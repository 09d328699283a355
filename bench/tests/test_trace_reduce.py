"""The reduction from a profiler trace to the per-layer metrics: on small
made-up traces, and on a trace recorded on a TPU v5e chip (one 5-round
chunk of ``sc2-silo-k2``, with the op names of the round program that
ran)."""
import gzip
import json
from pathlib import Path

import pytest

from bench import counts, trace_reduce as tr
from bench.harness import MetricContext
from bench.peaks import peaks_for
from bench.spec import metric_reader

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "sc2-silo-k2.xplane.pb"


def op(start, end, name, stack="", self_ns=None):
    return tr.Op(start, end, end - start if self_ns is None else self_ns, name,
                 (stack,) if stack else ())


def trace(ops, window=(0, 100), spans=()):
    return tr.Trace({0: ops}, sorted(spans), window)


def test_self_times_subtract_nested_ops():
    events = [(0, 100, "while.1"), (10, 30, "fusion.1"), (40, 50, "fed_direction_flat.2"),
              (60, 90, "while.2"), (70, 80, "copy.3")]
    got = {n: s for _, _, n, s in tr._self_times(events)}
    assert got == {"while.1": 100 - 20 - 10 - 30, "fusion.1": 20, "fed_direction_flat.2": 10,
                   "while.2": 20, "copy.3": 10}


def test_busy_and_idle_share():
    t = trace([op(10, 40, "a"), op(20, 30, "b", self_ns=10), op(60, 70, "c")])
    assert tr.busy_s(t) == pytest.approx(40e-9)


def test_layer_time_by_scope():
    t = trace([op(0, 10, "fed_direction_flat.1", "jit(run)/jit(fed_direction_flat)/pallas_call"),
               op(10, 15, "pad.2", "jit(run)/jit(fed_direction_flat)/jit(_pad)/pad"),
               op(15, 40, "fusion.3", "jit(run)/while/body/dot_general")])
    assert tr.layer_s(t, ("fed_direction_flat",)) == pytest.approx(15e-9)
    assert tr.layer_s(t, ("server_update_flat",)) is None


def test_mixed_fusions():
    inside, outside = "jit(r)/jit(fed_direction_flat)/mul", "jit(r)/add"
    t = tr.Trace({0: [tr.Op(0, 10, 10, "fusion.1", (outside, inside, outside)),
                      tr.Op(10, 20, 10, "fusion.2", (inside, inside))]}, [], (0, 20))
    assert tr.mixed_s(t, ("fed_direction_flat",)) == pytest.approx(10e-9)


def test_collectives_count_self_time():
    t = trace([op(0, 10, "all_to_all.1"), op(10, 30, "fusion.2"), op(30, 35, "all-gather-done.3"),
               op(35, 38, "all-gather.4")])
    assert tr.self_s(t, tr.is_collective) == pytest.approx(18e-9)
    assert tr.self_s(trace([op(0, 1, "fusion.1")]), tr.is_collective) is None


def test_breakdown_names_gaps_by_host_span():
    spans = [(0, 100, "bench.chunk"), (50, 70, "bench.metrics_to_host")]
    t = trace([op(0, 50, "fusion.1"), op(80, 90, "fusion.2")], spans=spans)
    b = tr.breakdown(t)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(50e-9)]
    assert b["idle_gaps"] == [["bench.metrics_to_host", pytest.approx(30e-9)],
                              ["bench.chunk", pytest.approx(10e-9)]]


def test_op_names_from_hlo():
    text = "\n".join([
        "%fused_computation.9 (param_0: f32[8]) -> f32[8] {",
        '  %pad.1 = f32[8]{0} pad(%param_0), metadata={op_name="jit(r)/jit(fed_direction_flat)/pad"}',
        '  ROOT %add.2 = f32[8]{0} add(%pad.1), metadata={op_name="jit(r)/add"}',
        "}",
        "ENTRY %main.3 (x: f32[8]) -> f32[8] {",
        '  ROOT %fusion.4 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.9, '
        'metadata={op_name="jit(r)/add"}',
        "}",
    ])
    names = tr.op_names_from_hlo(text)
    assert names["fusion.4"] == ["jit(r)/add", "jit(r)/jit(fed_direction_flat)/pad", "jit(r)/add"]
    assert names["pad.1"] == ["jit(r)/jit(fed_direction_flat)/pad"]


@pytest.fixture(scope="module")
def recorded():
    names = json.loads(gzip.decompress((DATA / "sc2-silo-k2.op_names.json.gz").read_bytes()))
    return tr.read(RECORDED, names)


def test_recorded_trace(recorded):
    t = recorded
    assert list(t.ops) == [0]
    assert 0.5 < t.window_s < 5
    busy = tr.busy_s(t)
    assert 0.9 * t.window_s < busy <= t.window_s
    # self times of nested operations add up to the busy time
    assert sum(o.self_ns for o in t.ops[0]) * 1e-9 == pytest.approx(busy, rel=1e-3)
    assert tr.layer_s(t, ("fed_direction_flat",)) > 0
    assert tr.layer_s(t, ("server_update_flat",)) > 0
    b = tr.breakdown(t)
    assert len(b["device_ops"]) == 10 and all(s > 0 for _, s in b["device_ops"])
    assert all(name.startswith("bench.") for name, _ in b["idle_gaps"])


def test_recorded_trace_metrics(recorded):
    cfg = json.loads((Path(__file__).resolve().parents[1] / "configs"
                      / "starcoder2-7b-1l.json").read_text())
    rounds = 5
    per_round = counts.lm_round(cfg, 4, 512, 2, 1)
    ctx = MetricContext(recorded, rounds, 1, peaks_for("TPU v5 lite"),
                        {k: v * rounds for k, v in per_round.items()})
    got = {m: metric_reader(m).read(ctx) for m in
           ("device_idle_share", "mfu", "fed_direction_roofline", "server_fold_roofline",
            "collective_exposed_ms")}
    assert got["collective_exposed_ms"] is None  # one chip: no exchange
    for m in ("device_idle_share", "mfu", "fed_direction_roofline", "server_fold_roofline"):
        assert 0 < got[m] < 100, (m, got[m])
