"""Benchmark aggregator: `PYTHONPATH=src python -m benchmarks.run [--quick]`.

Runs one benchmark per paper table/figure plus the kernel accounting and —
if dry-run artifacts exist — the roofline table.  ``--quick`` trims rounds
and seeds for CI-speed runs; the full protocol (150 rounds × 2 seeds) is
what EXPERIMENTS.md records.

Everything runs in this one process, and a phase that fails raises, so
the run exits non-zero.  The cohort-parallel sweep
(``benchmarks.cohort_sharded``) is not a phase: it needs several devices
from the moment JAX starts, so it runs as its own process (CI sets
``XLA_FLAGS`` for it).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="short rounds/seeds")
    ap.add_argument("--skip-fed", action="store_true")
    args = ap.parse_args()

    rounds = 60 if args.quick else 150
    seeds = 1 if args.quick else 2
    t0 = time.time()

    print("=" * 72)
    print("BENCHMARK 1/6 — Table 1/2 (scaled): main algorithm comparison")
    print("=" * 72)
    if not args.skip_fed:
        from benchmarks.table1_main_comparison import main as t1

        t1(rounds=rounds, seeds=seeds)

    print("\n" + "=" * 72)
    print("BENCHMARK 2/6 — Table 3 + Fig 1 (scaled): FedCM alpha sensitivity")
    print("=" * 72)
    if not args.skip_fed:
        from benchmarks.table3_alpha_sensitivity import main as t3

        t3(rounds=rounds, seeds=seeds)

    print("\n" + "=" * 72)
    print("BENCHMARK 3/6 — participation robustness sweep")
    print("=" * 72)
    if not args.skip_fed:
        from benchmarks.participation_robustness import main as pr

        pr(rounds=rounds, seeds=seeds)

    print("\n" + "=" * 72)
    print("BENCHMARK 3b — convergence vs uplink bits (compressed wire)")
    print("=" * 72)
    if not args.skip_fed:
        from benchmarks.convergence_bits import main as cb

        cb(rounds=20 if args.quick else 40)

    print("\n" + "=" * 72)
    print("BENCHMARK 4/6 — kernel accounting + correctness at size")
    print("=" * 72)
    from benchmarks.kernel_microbench import main as km

    km()

    print("\n" + "=" * 72)
    print("BENCHMARK 5/6 — fused run_rounds scan vs per-round dispatch")
    print("=" * 72)
    if not args.skip_fed:
        from benchmarks.fused_rounds import main as fr

        # quick: fewer rounds AND fewer interleaved timing repetitions —
        # fused_rounds now measures two workloads (tree vs flat per each)
        fr(rounds=20 if args.quick else 60, alts=2 if args.quick else 8)

    print("\n" + "=" * 72)
    print("BENCHMARK 6/6 — roofline table (from dry-run artifacts)")
    print("=" * 72)
    from benchmarks.roofline import load_rows

    rows = load_rows("single_pod_16x16")
    if rows:
        cols = ["arch", "shape", "kind", "compute_ms", "memory_ms",
                "collective_ms", "bottleneck", "model_flops_ratio"]
        w = {c: max(len(c), max(len(str(r[c])) for r in rows)) for c in cols}
        print("  ".join(c.ljust(w[c]) for c in cols))
        for r in sorted(rows, key=lambda r: (r["arch"], r["shape"])):
            print("  ".join(str(r[c]).ljust(w[c]) for c in cols))
    else:
        print("(no dry-run artifacts yet — run `python -m repro.launch.dryrun --all`)")

    print(f"\nall benchmarks done in {time.time() - t0:.0f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
