"""The harness's contract that needs no chip: it refuses to run without
one, and every name in BENCHMARK.json leads to the files it needs."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", "sc2-silo-k2",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files(w):
    bench = ROOT / "bench"
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    conf = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    config = json.loads((ROOT / conf["file"]).read_text())
    assert (bench / "families" / f"{config['family']}.py").is_file()
    assert (bench / "traffic" / f"{w['traffic']}.json").is_file()
    limits = json.loads((bench / "limits" / f"{w['name']}.json").read_text())
    assert limits and set(limits) <= {"loss_gap", "momentum_gap", "step_gap"}
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_metric_readers_and_units():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"rounds_per_s", "setup_s"}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
