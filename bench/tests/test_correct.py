"""``correct`` at a size a CPU test can hold.

The harness runs without its look for a chip: sound runs come out correct;
the control (the reference one precision below the configuration's) and
each fault the cells can have, planted in the program underneath the
timed path, come out wrong."""
import json
import math
import time
from pathlib import Path

import jax
import pytest

from bench import compare, harness
from bench.calibrate import calibrate
from bench.spec import Spec

BENCH = Path(__file__).resolve().parents[1]
LIMITS = {"loss_gap": 1e-3, "momentum_gap": 1e-2, "step_gap": 1e-2}


def tiny(shards: int = 0) -> Spec:
    """The StarCoder2 cells' configuration and traffic with the sizes cut
    to fit a test; the structure of the run is the cells'."""
    config = json.loads((BENCH / "configs" / "starcoder2-7b-1l.json").read_text())
    config.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, vocab_size=256)
    traffic = json.loads((BENCH / "traffic" / "silo-k2.json").read_text())
    traffic.update(data={"seqs_per_client": 8, "seq_len": 32}, chunk=3)
    if shards:
        traffic["fed"].update(cohort_size=shards, cohort_shard=shards)
    return Spec(workload={"name": f"tiny-{shards}", "chips": max(1, shards)}, config=config,
                traffic=traffic, limits=LIMITS, end_to_end=[], per_layer=[])


def run(spec, seed=2**31 + 11):
    return harness.run(spec, seed, 0.5, False, t0=time.perf_counter(), require_tpu=False)


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Programs compiled for the CPU stay out of the checkout's cache."""
    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)


@pytest.mark.parametrize("shards", [0, 4])
def test_sound_run_is_correct(shards):
    r = run(tiny(shards))
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r["checks"]) == list(compare.NUMBERS)


def test_a_number_left_out_is_not_compared(monkeypatch):
    _frozen(monkeypatch)
    spec = tiny()
    spec.limits = {"loss_gap": 1.0}  # the frozen state reads 1 on the other two
    r = run(spec)
    assert r["correct"] and list(r["checks"]) == ["loss_gap"]


def test_control_reads_far_above_the_program():
    """The control fails at least one number by three times the largest
    sound reading, on each of three seeds."""
    rows = calibrate(tiny(), [3, 4, 2**31 + 5], 3, [], require_tpu=False, emit=lambda _: None)
    prog = [r for r in rows if r["kind"] == "program"]
    ctrl = [r for r in rows if r["kind"].startswith("control")]
    assert len(ctrl) == 3
    lower = {k: max(r[k] for r in prog) for k in compare.NUMBERS}
    for r in ctrl:
        assert any(r[k] >= 3 * lower[k] for k in compare.NUMBERS), (r, lower)


def _frozen(monkeypatch):
    """A round that returns its state unchanged."""
    from repro.core.engine import FederatedEngine

    orig = FederatedEngine._flat_round_step

    def step(self, fstate, *a, **k):
        out = orig(self, fstate, *a, **k)
        same = fstate._replace(server=fstate.server._replace(round=out[0].server.round))
        return (same,) + tuple(out[1:])

    monkeypatch.setattr(FederatedEngine, "_flat_round_step", step)


def _half_batch(monkeypatch):
    """Half of each minibatch left out; the loss is the mean over the rest."""
    from repro.core.engine import FederatedEngine

    monkeypatch.setattr(FederatedEngine, "_to_loss_batches", staticmethod(
        lambda raw: {k: v[:, :, : v.shape[2] // 2] for k, v in raw.items()}))


def _no_exchange(monkeypatch):
    """The sharded fold without its exchange between chips: each chip
    folds its plane columns from its own clients' rows alone."""
    from repro.core import engine
    from repro.core.flat import gather_plane, plane_chunk
    from repro.kernels.server_update.ops import fused_fold

    def local_fold(spec, cfg, planes, wn, n_active, x, m, eta_l, discount=1.0, *,
                   axis_name, n_shards):
        i = jax.lax.axis_index(axis_name)
        rows = next(iter(planes.values())).shape[0]
        P = x.shape[-1]
        cols = {k: jax.vmap(lambda r: plane_chunk(r, axis_name, n_shards))(v)
                for k, v in planes.items() if k in spec.fold_planes}
        wn_own = jax.lax.dynamic_slice(wn, (i * rows,), (rows,))
        new_x, new_m, mean = fused_fold(spec, cfg, cols, wn_own, n_active,
                                        plane_chunk(x, axis_name, n_shards),
                                        plane_chunk(m, axis_name, n_shards),
                                        eta_l, discount=discount)
        return tuple(gather_plane(v, axis_name, P) for v in (new_x, new_m, mean))

    monkeypatch.setattr(engine, "scatter_fold", local_fold)


@pytest.mark.parametrize("shards,fault", [
    (0, _frozen), (0, _half_batch), (4, _frozen), (4, _half_batch), (4, _no_exchange),
])
def test_broken_program_is_not_correct(monkeypatch, shards, fault):
    fault(monkeypatch)
    r = run(tiny(shards))
    assert not r["correct"], r["checks"]
    worst = max(c["value"] / c["limit"] for c in r["checks"].values())
    assert worst > 3 or math.isinf(worst)
