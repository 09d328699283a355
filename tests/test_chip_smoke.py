"""``chip_smoke.py`` on the CPU: it refuses to report, and its phases run.

The script's result line is for a TPU only, so on the CPU ``main`` must
exit 1 without it.  Its phases still run here at tiny sizes (kernels in
interpret mode), which keeps them from breaking between chip runs; the
only check they may fail here is the one for a Mosaic kernel in the
compiled round.  A kernel planted with a fault must fail the comparison
at the stated tolerance.
"""
import dataclasses
import importlib.util
from pathlib import Path

import pytest

from repro.configs.base import get_config, reduced
from repro.kernels.fed_direction import ops as fed_direction_ops

SCRIPT = Path(__file__).resolve().parents[1] / "chip_smoke.py"
TINY_DATA = dict(n_train=1_200, n_test=200)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_setting(smoke):
    return dataclasses.replace(smoke.PHASE_A, num_clients=12, cohort_size=3,
                               local_steps=3, rounds=4)


def _only_kernel_checks_fail(failures):
    assert failures, "a kernel-path round on the CPU holds no Mosaic kernel"
    assert all("no Pallas kernel" in f for f in failures), failures


def test_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_phase_a_runs(smoke, tiny_setting, capsys):
    failures = []
    smoke.phase_a(failures, base=tiny_setting, chunk=2, **TINY_DATA)
    _only_kernel_checks_fail(failures)
    out = capsys.readouterr().out
    assert "A[f32] kernel vs tree" in out and "A[int8] Mosaic vs interpreter" in out


def test_phase_b_runs(smoke, capsys):
    failures = []
    mcfg = dataclasses.replace(reduced(get_config("llama3.2-1b")), vocab_size=256)
    smoke.phase_b(failures, mcfg=mcfg, seq=32)
    _only_kernel_checks_fail(failures)
    assert "B kernel vs tree" in capsys.readouterr().out


def test_sharded_phases_run(smoke, tiny_setting, capsys):
    """The ``--chips 4`` phases on a one-device mesh: the whole sharded
    path runs degenerately, and sharded against unsharded stays bitwise."""
    failures = []
    smoke.phase_a_sharded(failures, shards=1, base=tiny_setting, chunk=2,
                          **TINY_DATA)
    mcfg = dataclasses.replace(reduced(get_config("llama3.2-1b")), vocab_size=256)
    smoke.phase_b_sharded(failures, shards=1, mcfg=mcfg, seq=32)
    _only_kernel_checks_fail(failures)
    out = capsys.readouterr().out
    state_line = next(l for l in out.splitlines()
                      if l.startswith("smoke A sharded vs unsharded state:"))
    assert state_line.endswith("max_abs_diff=0.0"), state_line
    assert "B[cohort_shard=1]" in out


def test_lm_client_keeps_published_widths(smoke):
    full, cut = get_config("llama3.2-1b"), smoke.lm_client_config()
    assert (cut.n_layers, cut.vocab_size) == (2, 16_032)
    for field in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                  "mlp_type", "dtype"):
        assert getattr(cut, field) == getattr(full, field), field


@pytest.mark.parametrize("phase", ["A", "B"])
def test_compare_catches_a_kernel_that_drops_momentum(smoke, tiny_setting,
                                                      monkeypatch, capsys, phase):
    """Plant the fault the comparisons exist for: the local-step kernel
    drops FedCM's momentum term (its aux coefficients zeroed).  Round 1
    carries zero momentum, so only later rounds can show it."""
    real = fed_direction_ops.fed_direction_flat

    def drops_momentum(x, g, auxes, coefs, **launch):
        return real(x, g, auxes, coefs.at[3:].set(0.0), **launch)

    monkeypatch.setattr(fed_direction_ops, "fed_direction_flat", drops_momentum)
    failures = []
    if phase == "A":
        smoke.phase_a(failures, base=tiny_setting, chunk=2, **TINY_DATA)
    else:
        mcfg = dataclasses.replace(reduced(get_config("llama3.2-1b")), vocab_size=256)
        smoke.phase_b(failures, mcfg=mcfg, seq=32)
    caught = [f for f in failures if "kernel vs tree: losses differ" in f]
    assert caught, (failures, capsys.readouterr().out)
