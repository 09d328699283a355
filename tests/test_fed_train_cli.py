"""fed_train CLI → FedConfig wiring (the PR-2 ``use_flat_plane`` gap).

The driver builds its FedConfig from argv in ``resolve_config``; a flag
that parses but never reaches the config silently trains with the default
(exactly what happened to ``--flat-plane``'s predecessor).  ``--dryrun``
persists the RESOLVED config to an artifact, so the wiring is asserted
end-to-end: argv in → artifact out, no training."""
import json

import pytest

from repro.configs.base import FedConfig
from repro.launch.fed_train import (
    DRYRUN_ARTIFACT,
    build_parser,
    main,
    resolve_config,
)


def _resolved(argv):
    return resolve_config(build_parser().parse_args(argv))


def test_flat_plane_flag_wires_through():
    assert _resolved([]).use_flat_plane is FedConfig.use_flat_plane
    assert _resolved(["--flat-plane"]).use_flat_plane is True
    assert _resolved(["--no-flat-plane"]).use_flat_plane is False


def test_async_flags_wire_through():
    cfg = _resolved(["--pipeline-depth", "4", "--staleness", "2",
                     "--staleness-discount", "0.9"])
    assert cfg.pipeline_depth == 4
    assert cfg.staleness == 2
    assert cfg.staleness_discount == pytest.approx(0.9)
    assert _resolved([]).pipeline_depth == 1 and _resolved([]).staleness == 0


def test_cohort_shard_flag_wires_through():
    assert _resolved([]).cohort_shard == 0
    cfg = _resolved(["--cohort-shard", "4"])
    assert cfg.cohort_shard == 4 and cfg.use_fused_kernel is True


def test_cohort_shard_requires_kernel_and_flat_plane():
    with pytest.raises(SystemExit):  # the kernels take no flag any more
        main(["--dryrun", "--cohort-shard", "2", "--fused-kernel"])
    with pytest.raises(SystemExit):  # the flat plane is required
        main(["--dryrun", "--cohort-shard", "2", "--no-flat-plane"])


def test_cohort_shard_dryrun_records_mesh(tmp_path, monkeypatch):
    art = tmp_path / "fed_train_dryrun.json"
    monkeypatch.setattr("repro.launch.fed_train.DRYRUN_ARTIFACT", art)
    rc = main(["--dryrun", "--cohort-shard", "2"])
    assert rc == 0
    got = json.loads(art.read_text())
    assert got["resolved_config"]["cohort_shard"] == 2
    assert got["cohort_mesh"] == {
        "axes": ["clients"], "shape": [2],
        "devices_visible": got["cohort_mesh"]["devices_visible"],
    }
    # no --cohort-shard → no mesh recorded
    rc = main(["--dryrun"])
    assert json.loads(art.read_text())["cohort_mesh"] is None


def test_dryrun_artifact_records_resolved_config(tmp_path, monkeypatch):
    art = tmp_path / "fed_train_dryrun.json"
    monkeypatch.setattr("repro.launch.fed_train.DRYRUN_ARTIFACT", art)
    rc = main(["--dryrun", "--no-flat-plane",
               "--pipeline-depth", "2", "--staleness", "1",
               "--algo", "scaffold", "--clients", "7"])
    assert rc == 0
    got = json.loads(art.read_text())["resolved_config"]
    assert got["use_flat_plane"] is False
    assert got["use_fused_kernel"] is True
    assert got["pipeline_depth"] == 2
    assert got["staleness"] == 1
    assert got["algo"] == "scaffold"
    assert got["num_clients"] == 7
    assert json.loads(art.read_text())["engine_mode"] == "async_pipeline"


def test_per_round_conflicts_with_async():
    """--per-round (one jit dispatch per round) and the async pipelined
    engine (one fused program) are mutually exclusive — combining them
    must error instead of silently dropping --per-round."""
    for argv in (["--per-round", "--pipeline-depth", "2"],
                 ["--per-round", "--staleness", "1"],
                 ["--per-round", "--async"]):
        with pytest.raises(SystemExit) as e:
            main(argv + ["--dryrun"])
        assert e.value.code == 2  # argparse error exit


def test_algo_choices_come_from_registry(capsys):
    """--algo choices ARE the registry: a freshly registered name parses,
    an unknown one errors naming the registered set."""
    from repro.core import list_algorithms

    assert tuple(
        build_parser()._option_string_actions["--algo"].choices
    ) == list_algorithms()
    assert _resolved(["--algo", "fedavgm"]).algo == "fedavgm"
    with pytest.raises(SystemExit) as e:
        build_parser().parse_args(["--algo", "nope"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "fedcm" in err and "fedavgm" in err  # the registry list, rendered


def test_list_algos_prints_registry(capsys):
    """--list-algos prints every registered spec's state planes + kernel
    routing and exits 0 without touching data or the engine."""
    from repro.core import list_algorithms

    assert main(["--list-algos"]) == 0
    out = capsys.readouterr().out
    for name in list_algorithms():
        assert name in out
    assert "fed_direction" in out and "server_update" in out
    assert "client_state" in out  # state-plane requirements rendered


def test_fault_flags_wire_through():
    """Fault knobs land on cfg.fault as a FaultConfig; all-defaults keeps
    fault=None (the bitwise-preserved engine)."""
    assert _resolved([]).fault is None
    cfg = _resolved(["--fault-drop-rate", "0.2", "--fault-corrupt-rate",
                     "0.05", "--fault-corrupt-mode", "inf",
                     "--fault-deadline", "2.0",
                     "--fault-store-failure-rate", "0.1",
                     "--fault-seed", "7"])
    assert cfg.fault is not None
    assert cfg.fault.drop_rate == pytest.approx(0.2)
    assert cfg.fault.corrupt_rate == pytest.approx(0.05)
    assert cfg.fault.corrupt_mode == "inf"
    assert cfg.fault.deadline == pytest.approx(2.0)
    assert cfg.fault.store_failure_rate == pytest.approx(0.1)
    assert cfg.fault.seed == 7
    # any single nonzero knob materializes the config
    assert _resolved(["--quarantine-norm-mult", "5.0"]).fault is not None


def test_quorum_and_empty_cohort_flags_wire_through():
    assert _resolved([]).min_quorum == 0
    assert _resolved([]).allow_empty_cohort is False
    cfg = _resolved(["--min-quorum", "3", "--allow-empty-cohort"])
    assert cfg.min_quorum == 3 and cfg.allow_empty_cohort is True


def test_fault_flags_reach_dryrun_artifact(tmp_path, monkeypatch):
    art = tmp_path / "fed_train_dryrun.json"
    monkeypatch.setattr("repro.launch.fed_train.DRYRUN_ARTIFACT", art)
    rc = main(["--dryrun", "--fault-drop-rate", "0.3",
               "--fault-corrupt-rate", "0.02", "--min-quorum", "2",
               "--ckpt-every", "10", "--ckpt-dir", str(tmp_path)])
    assert rc == 0
    got = json.loads(art.read_text())
    rc_cfg = got["resolved_config"]
    assert rc_cfg["fault"]["drop_rate"] == pytest.approx(0.3)
    assert rc_cfg["fault"]["corrupt_rate"] == pytest.approx(0.02)
    assert rc_cfg["min_quorum"] == 2
    assert got["ckpt_every"] == 10
    # no fault flags → fault stays null in the artifact
    assert main(["--dryrun"]) == 0
    assert json.loads(art.read_text())["resolved_config"]["fault"] is None


def test_ckpt_flag_validations():
    """Snapshot flags constrain each other: ckpt needs a dir and the fused
    chunk loop; die-after/resume need ckpt-every."""
    for argv in (["--ckpt-every", "5"],                      # no --ckpt-dir
                 ["--ckpt-every", "5", "--ckpt-dir", "/tmp/x", "--async"],
                 ["--ckpt-every", "5", "--ckpt-dir", "/tmp/x", "--per-round"],
                 ["--die-after", "5", "--ckpt-dir", "/tmp/x"],  # no ckpt-every
                 ["--resume", "--ckpt-dir", "/tmp/x"]):
        with pytest.raises(SystemExit) as e:
            main(argv + ["--dryrun"])
        assert e.value.code == 2


def test_dryrun_artifact_default_mode(tmp_path, monkeypatch):
    art = tmp_path / "fed_train_dryrun.json"
    monkeypatch.setattr("repro.launch.fed_train.DRYRUN_ARTIFACT", art)
    assert main(["--dryrun"]) == 0
    got = json.loads(art.read_text())
    assert got["resolved_config"]["use_flat_plane"] is True
    assert got["engine_mode"] == "fused_scan"
    assert main(["--dryrun", "--per-round"]) == 0
    assert json.loads(art.read_text())["engine_mode"] == "per_round"
    assert main(["--dryrun", "--async"]) == 0
    assert json.loads(art.read_text())["engine_mode"] == "async_pipeline"


def test_serve_flags_reach_dryrun_artifact(tmp_path, monkeypatch):
    art = tmp_path / "fed_train_dryrun.json"
    monkeypatch.setattr("repro.launch.fed_train.DRYRUN_ARTIFACT", art)
    rc = main(["--dryrun", "--serve", "--ckpt-every", "2",
               "--ckpt-dir", str(tmp_path), "--round-deadline", "45",
               "--publish-retain", "3"])
    assert rc == 0
    sv = json.loads(art.read_text())["serve"]
    assert sv["enabled"] is True
    assert sv["round_deadline_s"] == pytest.approx(45.0)
    assert sv["publish_retain"] == 3
    assert sv["publish_every"] == 2
    # telemetry path defaults into the ckpt dir
    assert sv["telemetry_path"] == str(tmp_path / "telemetry.jsonl")
    # without --serve the knobs are recorded but disabled
    assert main(["--dryrun"]) == 0
    sv = json.loads(art.read_text())["serve"]
    assert sv["enabled"] is False and sv["publish_every"] is None


def test_dryrun_telemetry_schema_agrees_with_fleet(tmp_path, monkeypatch):
    """The artifact's telemetry block IS the fleet schema — a rename in
    either place makes --dryrun and the written rows disagree loudly."""
    from repro.fleet.telemetry import (
        FAULT_COUNTERS, ROUND_FIELDS, TELEMETRY_SCHEMA,
    )
    from repro.core.engine import RoundMetrics

    art = tmp_path / "fed_train_dryrun.json"
    monkeypatch.setattr("repro.launch.fed_train.DRYRUN_ARTIFACT", art)
    assert main(["--dryrun"]) == 0
    tel = json.loads(art.read_text())["telemetry"]
    assert tel["schema"] == TELEMETRY_SCHEMA
    assert tel["round_fields"] == list(ROUND_FIELDS)
    assert tel["fault_counters"] == list(FAULT_COUNTERS)
    assert set(tel["fault_counters"]) <= set(RoundMetrics._fields)


def test_serve_flag_validations_cli():
    """--serve requires the snapshot cadence (its publish source) and a
    checkpoint dir; retention ring must keep >= 2 versions."""
    for argv in (["--serve"],                                   # no ckpt
                 ["--serve", "--ckpt-every", "2"],              # no dir
                 ["--serve", "--ckpt-every", "2", "--ckpt-dir", "/tmp/x",
                  "--publish-retain", "1"]):
        with pytest.raises(SystemExit) as e:
            main(argv + ["--dryrun"])
        assert e.value.code == 2


def test_dryrun_artifact_static_contracts(tmp_path, monkeypatch):
    art = tmp_path / "fed_train_dryrun.json"
    monkeypatch.setattr("repro.launch.fed_train.DRYRUN_ARTIFACT", art)
    assert main(["--dryrun"]) == 0
    sc = json.loads(art.read_text())["static_contracts"]
    assert sc["donation_ok"] is True
    assert sc["transfer_guard_ok"] is True
    assert sc["trace_count"] == sc["trace_budget"] == 1
    assert "sync" in sc["path"]
    assert main(["--dryrun", "--async"]) == 0
    sc = json.loads(art.read_text())["static_contracts"]
    assert sc["donation_ok"] is True
    assert "async" in sc["path"]


@pytest.mark.parametrize("env_dir", ["", "/somewhere/else"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """The env var wins and is left to jax; otherwise the fixed repo path."""
    import jax

    from repro.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, env_dir)
    try:
        got = compile_cache.use_compile_cache()
        if env_dir:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == str(compile_cache.REPO_CACHE_DIR)
            assert compile_cache.REPO_CACHE_DIR.name == ".jax_cache"
            assert (compile_cache.REPO_CACHE_DIR.parent / "src" / "repro").is_dir()
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
