"""The readers of the program's named scopes (``local_steps_ms``,
``plane_view_ms``, ``fold_ms``): on small made-up traces, and on a trace
recorded on a TPU v5e chip with the scopes in place (one 5-round chunk of
``sc2-silo-k2``, made by ``bench/record_trace.py``)."""
import gzip
import json
from pathlib import Path

import pytest

from bench import scopes, trace_reduce as tr
from bench.harness import MetricContext
from bench.spec import metric_reader

READERS = ("local_steps_ms", "plane_view_ms", "fold_ms")
DATA = Path(__file__).resolve().parent / "data"
BODY = "jit(_run_rounds_impl)/while/body/closed_call"


def op(ns, name, stack=""):
    return tr.Op(0, ns, ns, name, (stack,) if stack else ())


def read_all(ops_by_chip, rounds=1):
    t = tr.Trace(ops_by_chip, [], (0, 10**9))
    ctx = MetricContext(t, rounds, len(ops_by_chip), {}, {})
    return {m: metric_reader(m).read(ctx) for m in READERS}


def test_readers_name_their_scope_by_another_attribute():
    """The harness prints ``SCOPES`` as jit-wrapper names: these readers
    keep theirs under another name."""
    for m in READERS:
        reader = metric_reader(m)
        assert not hasattr(reader, "SCOPES")
        assert reader.PROGRAM_SCOPE in scopes.ORDER


@pytest.mark.parametrize("stack, scope", [
    (f"{BODY}/vmap()/while/body/closed_call/fedcm.local_steps/jvp()/dot_general",
     "fedcm.local_steps"),
    # the gradient's way back into the plane: the innermost scope wins
    (f"{BODY}/vmap()/while/body/closed_call/fedcm.local_steps/"
     "transpose(jvp(fedcm.plane_view))/add_any", "fedcm.plane_view"),
    (f"{BODY}/vmap()/while/body/closed_call/fedcm.local_steps/jvp(fedcm.plane_view)/slice",
     "fedcm.plane_view"),
    ("jit(_run_rounds_impl)/fedcm.plane_view/concatenate", "fedcm.plane_view"),
    (f"{BODY}/fedcm.fold/jit(server_update_flat)/pallas_call", "fedcm.fold"),
    (f"{BODY}/fedcm.fold/shard_map/all_to_all", "fedcm.fold"),
    (f"{BODY}/fedcm.fold/fedcm.plane_view/slice", "fedcm.plane_view"),
    (f"{BODY}/vmap()/while/body/closed_call/jit(fed_direction_flat)/pallas_call", None),
    ("", None),
])
def test_precedence(stack, scope):
    assert scopes.scope_of(op(1, "x.1", stack)) == scope


def test_partition_in_ms_per_round():
    ops = [op(3_000_000, "fusion.1", f"{BODY}/fedcm.local_steps/jvp()/dot_general"),
           op(2_000_000, "fusion.2", f"{BODY}/fedcm.local_steps/transpose(jvp(fedcm.plane_view))/pad"),
           op(1_000_000, "fusion.3", f"{BODY}/fedcm.fold/jit(server_update_flat)/mul"),
           op(4_000_000, "fed_direction_flat.4", f"{BODY}/jit(fed_direction_flat)/pallas_call"),
           op(5_000_000, "copy.5")]
    got = read_all({0: ops}, rounds=2)
    assert got == {"local_steps_ms": pytest.approx(1.5), "plane_view_ms": pytest.approx(1.0),
                   "fold_ms": pytest.approx(0.5)}


def test_a_fusion_counts_by_its_own_op_name():
    fused = tr.Op(0, 10, 10, "fusion.1", (f"{BODY}/fedcm.fold/add",
                                          f"{BODY}/fedcm.local_steps/mul", f"{BODY}/fedcm.fold/add"))
    got = read_all({0: [fused]})
    assert got["fold_ms"] == pytest.approx(1e-5) and got["local_steps_ms"] is None


def test_none_where_nothing_matched():
    got = read_all({0: [op(1000, "fusion.1", f"{BODY}/jit(fed_direction_flat)/mul"),
                        op(1000, "copy.2")]})
    assert got == dict.fromkeys(READERS)


def test_averaged_over_chips():
    stack = f"{BODY}/fedcm.fold/shard_map/all_gather"
    got = read_all({0: [op(4_000_000, "all-gather.1", stack)],
                    1: [op(2_000_000, "all-gather.1", stack)],
                    2: [op(2_000_000, "all-gather.1", stack)],
                    3: [op(0, "fusion.2")]}, rounds=1)
    assert got["fold_ms"] == pytest.approx(2.0)
    assert got["plane_view_ms"] is None


@pytest.fixture(scope="module")
def recorded():
    stem = DATA / "sc2-silo-k2-scoped"
    names = json.loads(gzip.decompress(Path(f"{stem}.op_names.json.gz").read_bytes()))
    return tr.read(f"{stem}.xplane.pb", names)


def test_recorded_trace(recorded):
    rounds = 5
    got = {m: metric_reader(m).read(MetricContext(recorded, rounds, 1, {}, {}))
           for m in READERS}
    assert got == {"local_steps_ms": pytest.approx(89.532, rel=1e-3),
                   "plane_view_ms": pytest.approx(10.831, rel=1e-3),
                   "fold_ms": pytest.approx(31.803, rel=1e-3)}
    # the fold's scope takes in the fold kernel's jit scope
    kernel = tr.layer_s(recorded, ("server_update_flat",))
    assert 1e3 * kernel / rounds <= got["fold_ms"] + 1e-9
    # the share of the round program's device self time that the three
    # scopes and the local update hold, as PERF.md states it (83.6%)
    cov = scopes.coverage(recorded)
    assert cov["share"] == pytest.approx(0.836, abs=0.001)
    assert cov["round_program"] == pytest.approx(1.0367, rel=1e-3)  # s over the 5 rounds
