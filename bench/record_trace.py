"""Record one traced window of a cell and keep it, for the trace tests.

    python3 bench/record_trace.py --workload <cell> --seed <n> --out <path stem>

Runs the cell's set-up and a traced window of one chunk as a
``--trace 1`` run does (``harness.start``, ``traced_window``), then writes
``<stem>.xplane.pb`` (the profiler's trace) and ``<stem>.op_names.json.gz``
(the name stacks of the round program the window ran, for the instructions
the trace holds), which ``trace_reduce.read`` takes back.  Prints the
per-layer metrics of the recording and the share of the round program's
device self time that the program's scopes and the local update hold.
Needs the chips the cell asks for."""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, scopes, trace_reduce  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402
from bench.spec import family, load, metric_reader  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True, help="path stem of the two files")
    args = ap.parse_args(argv)

    spec = load(ROOT, args.workload)
    chips = spec.workload["chips"]
    dev = harness.devices_for(chips, require_tpu=True)[0]
    harness.use_compile_cache()
    cell = family(spec.config).build(spec.config, spec.traffic, chips)
    runner, _, _ = harness.start(cell, args.seed)
    harness.say(f"record: setup_s={time.perf_counter() - T0:.4f}")
    out_dir = harness.TRACE_DIR / f"record-{args.workload}-{args.seed}"
    traced = harness.traced_window(cell, runner, 1, out_dir)

    names = trace_reduce.op_names_from_hlo(harness.round_program(cell, runner).as_text())
    tr = trace_reduce.read(traced["path"], names)
    ran = {o.name for ops in tr.ops.values() for o in ops}
    stem = Path(args.out)
    stem.parent.mkdir(parents=True, exist_ok=True)
    shutil.copyfile(traced["path"], f"{stem}.xplane.pb")
    kept = {k: v for k, v in names.items() if k in ran}
    Path(f"{stem}.op_names.json.gz").write_bytes(
        gzip.compress(json.dumps(kept, sort_keys=True).encode(), mtime=0))
    shutil.rmtree(out_dir, ignore_errors=True)

    ctx = harness.MetricContext(tr, traced["attempted"], chips, peaks_for(dev.device_kind),
                                traced["work"])
    metrics = {m["name"]: metric_reader(m["name"]).read(ctx) for m in spec.per_layer}
    print(json.dumps({"rounds": traced["attempted"], "window_s": tr.window_s,
                      "metrics": metrics, "coverage": scopes.coverage(tr)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
