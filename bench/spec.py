"""Everything one cell needs, found by name through ``BENCHMARK.json``:
its entry, its configuration's file, its traffic mix
(``bench/traffic/<traffic>.json``), the limits of its comparison
(``bench/limits/<cell>.json``), its family module and the readers of its
per-layer metrics (``bench/metrics/<metric>.py``)."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Spec:
    workload: dict
    config: dict  # the configuration's file
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list  # the per-layer metric entries this cell reports


def load_module(path: Path):
    """Import a file of the benchmark by its path (names may hold '-')."""
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, workload: dict) -> bool:
    return workload["name"] in metric.get("workloads", [workload["name"]])


def load(root: Path, name: str) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    workloads = {w["name"]: w for w in bench["workloads"]}
    if name not in workloads:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(workloads)}")
    w = workloads[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    return Spec(
        workload=w,
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()),
        end_to_end=bench["end_to_end"],
        per_layer=[m for m in bench["per_layer"] if _reports(m, w)],
    )


def family(config: dict):
    return load_module(BENCH / "families" / f"{config['family']}.py")


def metric_reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py")
