"""One run of one cell: set-up, the measured window, the comparison.

Set-up makes the weights on the device and the data on the host from the
seed, builds the program's engine and runs the cell's first chunk through
the same call the window drives; that chunk compiles every program the
window uses, and its results are what the reference is compared with.
The window then runs whole chunks until ``seconds`` have passed.  With
``trace`` a short traced window of ``trace_chunks`` chunks takes its place
and the per-layer metrics are read from its profiler trace.  Once the
window has closed and the peak memory has been read, the program's state
is dropped and the plain reference follows the first chunk's rounds.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec

from bench import compare, trace_reduce
from bench.peaks import peaks_for
from bench.reference import fedcm as ref_fedcm
from bench.spec import family, load, metric_reader
from repro.core.engine import metrics_to_host

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_traces"


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


class CompileLog:
    """Counts XLA compilations (``jax.monitoring``) from the moment it is
    made; ``mark()`` starts a new count."""

    def __init__(self):
        self.count = self.seconds = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    def mark(self):
        out = (self.count, self.seconds)
        self.count = self.seconds = 0
        return out


def use_compile_cache():
    """JAX's persistent cache at one fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names another; every program is kept."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def seeds(seed: int):
    """Independent 31-bit seeds for the weights, the run's key and the
    data, from any whole number."""
    w, r, d = (int(s) & 0x7FFFFFFF for s in np.random.SeedSequence(seed).generate_state(3))
    return jax.random.PRNGKey(w), jax.random.PRNGKey(r), d


def devices_for(chips: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, {len(devs)} visible")
    return devs[:chips]


@dataclasses.dataclass
class MetricContext:
    trace: trace_reduce.Trace
    rounds: int
    chips: int
    peaks: dict
    work: dict  # bench.counts totals over the traced rounds


class Runner:
    """The cell's program with its state, and the window's one call."""

    def __init__(self, cell, state, data):
        self.cell, self.state, self.data = cell, state, data

    def chunk(self):
        with jax.profiler.TraceAnnotation("bench.chunk"):
            with jax.profiler.TraceAnnotation("bench.run_rounds"):
                self.state, ms = self.cell.engine.run_rounds(self.state, self.data, self.cell.chunk)
            with jax.profiler.TraceAnnotation("bench.metrics_to_host"):
                host = metrics_to_host(ms)
        return host


def start(cell, seed: int):
    """Weights, data and state of one seed, and the first chunk through
    the window's own call.  Returns (runner, host data, the program's
    readings for the comparison)."""
    wkey, rkey, dseed = seeds(seed)
    host_data = cell.make_data(dseed)
    arrays = [jax.numpy.asarray(host_data[k]) for k in ("client_x", "client_y")]
    if cell.engine.cohort_mesh is not None:
        arrays = jax.device_put(arrays, NamedSharding(cell.engine.cohort_mesh, PartitionSpec()))
    data = SimpleNamespace(client_x=arrays[0], client_y=arrays[1])
    runner = Runner(cell, cell.engine.init(cell.init_params(wkey), rkey), data)
    host = runner.chunk()
    first = compare.summarize(host["loss"], runner.state.params, runner.state.server.momentum,
                              cell.init_params(wkey))
    jax.block_until_ready(runner.state)
    return runner, host_data, first


def reference(cell, seed: int, host_data, mode: str = "f32", fault=None) -> dict:
    """The plain reference's readings over the first chunk's rounds: in
    ``mode`` precision, with ``fault`` planted (bench.reference.fedcm)."""
    wkey, rkey, _ = seeds(seed)
    shards = max(1, cell.fed.get("cohort_shard", 0))
    out = ref_fedcm.run_rounds(
        cell.ref_loss, cell.init_params(wkey), jax.numpy.asarray(host_data["client_x"]),
        jax.numpy.asarray(host_data["client_y"]), rkey, cell.fed, cell.batch_size, cell.chunk,
        mode=mode, fault=fault, shards=shards)
    return compare.summarize(out["losses"], out["params"], out["momentum"],
                             cell.init_params(wkey))


def run(spec, seed: int, seconds: float, trace: bool, *, t0: float,
        require_tpu: bool = True) -> dict:
    workload = spec.workload["name"]
    chips = spec.workload["chips"]
    devs = devices_for(chips, require_tpu)
    use_compile_cache()
    compiles = CompileLog()
    dev = devs[0]
    say(f"bench: {workload} seed={seed} device={dev.platform}/{dev.device_kind} x{len(devs)}")

    cell = family(spec.config).build(spec.config, spec.traffic, chips)
    runner, host_data, first = start(cell, seed)
    setup_s = time.perf_counter() - t0
    n_setup, s_setup = compiles.mark()
    say(f"bench: setup_s={setup_s:.4f} compiles={n_setup} compile_s={s_setup:.4f}")

    if trace:
        traced = traced_window(cell, runner, spec.traffic["trace_chunks"],
                               TRACE_DIR / f"{workload}-{seed}")
    else:
        result = timed_window(cell, runner, seconds, setup_s)
    n_win, s_win = compiles.mark()
    say(f"bench: compiles_in_window={n_win} compile_s_in_window={s_win:.4f}")
    if trace:
        result = per_layer(spec, cell, runner, traced, chips, dev)
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
                        "memory_peak_bytes": max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                                                 for d in devs)}
    if trace:
        result["device"].update(result.pop("_busy"))

    # the comparison, with the program's state dropped
    del runner
    gc.collect()
    values = compare.readings(first, reference(cell, seed, host_data))
    ok, checks = compare.judge(values, spec.limits)
    result["correct"] = ok and result["failed"] == 0
    result["checks"] = checks
    for k in compare.NUMBERS:
        if k not in checks:
            say(f"reading {k}: {values[k]!r} (not compared)")
    for k, c in checks.items():
        say(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    return result


def timed_window(cell, runner, seconds, setup_s) -> dict:
    rounds = failed = 0
    start = last = time.perf_counter()
    chunk_s = []
    while True:
        host = runner.chunk()
        rounds += cell.chunk
        failed += int(np.sum(~np.isfinite(host["loss"])))
        now = time.perf_counter()
        chunk_s.append(now - last)
        last = now
        if now - start >= seconds:
            break
    jax.block_until_ready(runner.state)
    elapsed = time.perf_counter() - start
    q = np.quantile(chunk_s, [0.0, 0.5, 1.0])
    say(f"bench: window {rounds} rounds in {elapsed:.4f} s; chunk s min/median/max "
        f"{q[0]:.5f}/{q[1]:.5f}/{q[2]:.5f}")
    return {"attempted": rounds, "failed": failed, "metrics": {
        "rounds_per_s": {"value": rounds / elapsed, "unit": "rounds/s"},
        "setup_s": {"value": setup_s, "unit": "s"}}}


def traced_window(cell, runner, n_chunks: int, out_dir: Path) -> dict:
    """``n_chunks`` chunks under the profiler; the trace goes to ``out_dir``."""
    shutil.rmtree(out_dir, ignore_errors=True)
    rounds = failed = 0
    work = {}
    jax.profiler.start_trace(str(out_dir))
    try:
        for _ in range(n_chunks):
            host = runner.chunk()
            rounds += cell.chunk
            failed += int(np.sum(~np.isfinite(host["loss"])))
            for n in host["n_active"]:
                for k, v in cell.work(float(n)).items():
                    work[k] = work.get(k, 0) + v
        jax.block_until_ready(runner.state)
    finally:
        jax.profiler.stop_trace()
    return {"attempted": rounds, "failed": failed, "work": work,
            "path": next(out_dir.rglob("*.xplane.pb"))}


def per_layer(spec, cell, runner, traced, chips, dev) -> dict:
    """The per-layer metrics and the breakdown from the traced window,
    with the name stacks read from the round program the window ran."""
    compiled = round_program(cell, runner)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    say(f"bench: round program holds tpu_custom_call: {'tpu_custom_call' in text}; "
        + " ".join(f"{k}_bytes={getattr(mem, f'{k}_size_in_bytes')}"
                   for k in ("argument", "output", "alias", "temp")))
    names = trace_reduce.op_names_from_hlo(text)
    tr = trace_reduce.read(traced["path"], names)
    ctx = MetricContext(tr, traced["attempted"], chips, peaks_for(dev.device_kind),
                        traced["work"])
    metrics = {}
    for m in spec.per_layer:
        v = metric_reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    for m in spec.per_layer:
        scopes = getattr(metric_reader(m["name"]), "SCOPES", None)
        if scopes:
            say(f"bench: {m['layer']}: attributed {trace_reduce.layer_s(tr, scopes)!r} s, "
                f"in mixed fusions {trace_reduce.mixed_s(tr, scopes)!r} s")
    say(f"bench: traced {traced['attempted']} rounds in {tr.window_s!r} s")
    shutil.rmtree(traced["path"].parents[3], ignore_errors=True)
    return {"attempted": traced["attempted"], "failed": traced["failed"], "metrics": metrics,
            "breakdown": trace_reduce.breakdown(tr),
            "_busy": {"busy_s": trace_reduce.busy_s(tr), "window_s": tr.window_s}}


def round_program(cell, runner):
    """The round program the window ran, compiled again (the compilation
    cache answers) for its optimized HLO and memory analysis."""
    lowered = cell.engine._run_rounds.lower(runner.state, runner.data.client_x,
                                            runner.data.client_y, n_rounds=cell.chunk)
    return lowered.compile()


def main(argv=None, *, t0: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(load(ROOT, args.workload), args.seed, args.seconds, bool(args.trace), t0=t0)
    order = ("correct", "attempted", "failed", "metrics", "device", "breakdown", "checks")
    print(json.dumps({k: result[k] for k in order if k in result}), flush=True)
    return 0
