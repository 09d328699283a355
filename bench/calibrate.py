"""Readings that the limits of ``correct`` are set from, for one cell,
in one process:

- the program against the reference on each of ``--seeds`` (the lower
  readings: sound runs);
- the control, the reference computed one precision below the
  configuration's, against the reference, on the first ``--control``
  seeds (the upper readings);
- each planted fault of the reference (``--faults``) against the
  reference, on the same seeds.

Each reading is also judged by ``compare.judge`` at the cell's committed
limits (``bench/limits/<cell>.json``), as a benchmark run judges it: a
sound run has to come out ``correct``, the control and every fault not.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 --control 3 \\
        --faults half_batch [--out FILE]

Each reading is printed as one JSON line; ``--out`` also writes them all
to one file.  The benchmark's own runs never run this."""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import compare, harness  # noqa: E402
from bench.spec import family, load  # noqa: E402


def calibrate(spec, seeds, n_control, faults, *, require_tpu=True, emit=print):
    harness.devices_for(spec.workload["chips"], require_tpu)
    harness.use_compile_cache()
    cell = family(spec.config).build(spec.config, spec.traffic, spec.workload["chips"])
    rows = []

    def record(kind, seed, values):
        correct, _ = compare.judge(values, spec.limits)
        row = {"workload": spec.workload["name"], "kind": kind, "seed": seed, **values,
               "correct": correct}
        rows.append(row)
        emit(json.dumps(row))

    for i, seed in enumerate(seeds):
        runner, host_data, first = harness.start(cell, seed)
        del runner
        gc.collect()
        ref = harness.reference(cell, seed, host_data)
        record("program", seed, compare.readings(first, ref))
        if i < n_control:
            record(f"control:{cell.control}", seed,
                   compare.readings(harness.reference(cell, seed, host_data, mode=cell.control), ref))
            for fault in faults:
                record(f"fault:{fault}", seed,
                       compare.readings(harness.reference(cell, seed, host_data, fault=fault), ref))
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control", type=int, default=3, help="seeds that also run the control")
    ap.add_argument("--faults", default="", help="comma-separated planted faults")
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    spec = load(ROOT, args.workload)
    rows = calibrate(spec, [int(s) for s in args.seeds.split(",")], args.control,
                     [f for f in args.faults.split(",") if f])
    if args.out:
        Path(args.out).write_text("".join(json.dumps(r) + "\n" for r in rows))
    print(f"calibrate: {len(rows)} readings in {time.perf_counter() - T0:.1f} s", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
