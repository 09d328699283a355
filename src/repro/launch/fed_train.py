"""Federated training driver — the paper's experiment runner.

Runs any REGISTERED algorithm (``repro.core.registry``; ``--list-algos``
prints each spec's state planes + kernel routing, ``--algo`` choices are
the registry itself) on Dirichlet-partitioned synthetic classification
(paper §6.1 scaled; see EXPERIMENTS.md §Repro) or on a federated LM task
where every client holds a *different* Markov chain (natural
heterogeneity).

Rounds between evaluations execute as ONE fused ``engine.run_rounds`` scan
(cohort sampling + minibatch draws on-device, state donated) — per-round
python dispatch only happens with ``--per-round``, kept for A/B timing
against the fused path (benchmarks/fused_rounds.py measures the gap).

With ``--pipeline-depth D`` / ``--staleness S`` (or ``--async``) the run
switches to the overlapping-cohort engine ``run_rounds_async``: ONE
pipelined scan for the whole run, with evaluation device-resident INSIDE
the scan at the ``--eval-every`` cadence — zero host round-trips between
round 0 and the final metrics fetch.

``--population-store host`` switches to the out-of-core population engine
(``run_rounds_store``): per-client state lives in a sparse host store
(gathered/scattered per cohort as ``(C, P)`` blocks) and client shards
stream on demand (``repro.data.population``), so ``--num-clients 1000000``
runs without any ``(N, ·)`` device array.  ``--availability`` picks the
cohort-sampling process (zipf traffic skew, time-of-day sinusoid);
``--dropout-rate`` adds straggler dropout.  Both work on the resident
engine too.

``--dryrun`` resolves the full config, writes it (plus the engine's
payload accounting) to ``benchmarks/artifacts/fed_train_dryrun.json``, and
exits without training — the artifact is how CLI-flag wiring is asserted
in tests (a flag that never reaches FedConfig, like the PR-2
``use_flat_plane`` gap, shows up as a wrong resolved value here).

    PYTHONPATH=src python -m repro.launch.fed_train --algo fedcm \
        --clients 100 --cohort 10 --rounds 100 --dirichlet 0.6
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
from pathlib import Path

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, load_fed_run, save_fed_run
from repro.configs.base import CompressionConfig, FaultConfig, FedConfig
from repro.core import (
    FederatedEngine,
    RoundMetrics,
    describe_algorithm,
    get_algorithm,
    list_algorithms,
    make_eval_fn,
)
from repro.core.engine import metrics_to_host
from repro.fleet.telemetry import FAULT_COUNTERS, ROUND_FIELDS, TELEMETRY_SCHEMA
from repro.data import FederatedData, StreamingClientData, make_synthetic_classification
from repro.data.population import AVAILABILITY_PROCESSES, POPULATION_STORES
from repro.models.small import classification_loss, mlp_classifier
from repro.utils.compile_cache import use_compile_cache
from repro.utils.metrics import MetricLogger

DRYRUN_ARTIFACT = (
    Path(__file__).resolve().parents[3] / "benchmarks" / "artifacts"
    / "fed_train_dryrun.json"
)


def run_federated(
    cfg: FedConfig,
    dirichlet: float,
    *,
    dim: int = 32,
    n_classes: int = 10,
    n_train: int = 50_000,
    n_test: int = 10_000,
    batch_size: int = 50,
    hidden: int = 128,
    eval_every: int = 25,
    seed: int = 0,
    echo: bool = True,
    fused: bool = True,
    async_pipeline: bool = False,
    ckpt_every: int = 0,
    ckpt_dir: str = "",
    resume: bool = False,
    die_after: int = 0,
    serve: bool = False,
    health_port: int = 0,
    round_deadline_s: float = 120.0,
    telemetry_path: str = "",
    publish_retain: int = 4,
):
    """Returns (final_test_acc, history MetricLogger).

    ``ckpt_every`` > 0 publishes an atomic whole-run snapshot (FedState +
    host population store, one ``save_fed_run`` file) every N rounds on
    the fused path; ``resume`` restores the latest snapshot from
    ``ckpt_dir`` and CONTINUES the trajectory bitwise (same fused-scan
    chunking relative to absolute round).  ``die_after`` R kills the
    process with exit code 75 right after the first snapshot at round
    ≥ R — the chaos half of the kill-and-resume CI smoke.

    ``serve`` turns the run into the round-to-serving fleet loop
    (``repro.fleet``): rounds keep the main thread, a serving thread
    continuously decodes against the latest published params (published
    at every ``ckpt_every`` boundary, hot-swapped atomically between
    decode steps), a health endpoint reports round liveness, and an
    append-only telemetry JSONL records one row per round.  Fleet is
    observation-only — the training trajectory is bit-identical with or
    without it.  The logged loss is the chunk's last round, unrounded;
    ``seconds`` is the chunk's wall time up to its metrics on the host
    (the first chunk includes tracing and compilation)."""
    if cfg.population_store == "host":
        # out-of-core path: no (N, n_per, …) device stack exists — shards
        # regenerate on demand per sampled cohort (label skew replaces the
        # Dirichlet partition; --dirichlet is a no-op here)
        data = StreamingClientData(cfg.num_clients, dim=dim,
                                   n_classes=n_classes, seed=seed)
        x_te, y_te = data.test_set(min(n_test, 2_000))
    else:
        x_tr, y_tr, x_te, y_te = make_synthetic_classification(
            n_classes=n_classes, dim=dim, n_train=n_train, n_test=n_test, seed=seed
        )
        data = FederatedData(x_tr, y_tr, cfg.num_clients, dirichlet_alpha=dirichlet, seed=seed)
    model = mlp_classifier((dim, hidden, hidden, n_classes))
    loss_fn = classification_loss(model.apply)
    eng = FederatedEngine(cfg, loss_fn, batch_size=batch_size)
    state = eng.init(model.init(jax.random.PRNGKey(seed)), jax.random.PRNGKey(seed + 1))
    evaluate = make_eval_fn(model.apply)

    log = MetricLogger(
        ["round", "algo", "loss", "test_acc", "n_active", "mb_down", "mb_up",
         "dropped", "quar", "retries", "qskip", "seconds"],
        echo=echo, echo_every=1,
    )
    x_te_j, y_te_j = jnp.asarray(x_te), jnp.asarray(y_te)
    acc = 0.0
    if async_pipeline:
        if cfg.population_store == "host":
            # store-backed async is a host loop (gathers/scatters between
            # rounds); in-scan eval doesn't exist — evaluate once at the end
            state, ms = eng.run_rounds_async(state, data, cfg.rounds)
            acc = evaluate(state.params, x_te_j, y_te_j)
            log.log(round=cfg.rounds, algo=cfg.algo,
                    loss=round(float(ms.loss[-1]), 4),
                    test_acc=round(acc, 4), n_active=int(ms.n_active[-1]),
                    mb_down=round(float(ms.bytes_down[-1]) / 2**20, 2),
                    mb_up=round(float(ms.bytes_up[-1]) / 2**20, 2))
            return acc, log
        # the WHOLE run — cohort overlap, minibatch draws, eval — is one
        # jitted pipelined scan; eval accuracies come back in the stacked
        # metrics (−1.0 off-cadence)
        state, ms = eng.run_rounds_async(
            state, data, cfg.rounds,
            eval_every=eval_every, eval_data=(x_te_j, y_te_j),
            predict_fn=model.apply,
        )
        accs = np.asarray(ms.eval_acc)
        for r in np.flatnonzero(accs >= 0.0):
            acc = float(accs[r])
            log.log(round=int(r) + 1, algo=cfg.algo,
                    loss=round(float(ms.loss[r]), 4),
                    test_acc=round(acc, 4), n_active=int(ms.n_active[r]),
                    mb_down=round(float(ms.bytes_down[r]) / 2**20, 2),
                    mb_up=round(float(ms.bytes_up[r]) / 2**20, 2))
        if (cfg.pipeline_depth > 1 or not np.any(accs >= 0.0)
                or (cfg.rounds % eval_every) != 0):
            # one host-side eval of the RETURNED params: the final round
            # fell off the eval cadence, or the pipeline drained after the
            # last in-scan eval (which sees pre-drain params — the
            # returned state additionally folds the ≤depth−1 cohorts
            # still in flight)
            acc = evaluate(state.params, x_te_j, y_te_j)
        return acc, log
    if fused:
        # eval_every rounds per jitted scan; metrics come back stacked and
        # we log the chunk's final round (same cadence as the --per-round path)
        r = 0
        if resume:
            step = latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"--resume: no checkpoints in {ckpt_dir!r}")
            state, population, residuals, meta = load_fed_run(
                ckpt_dir, step, state, num_clients=cfg.num_clients
            )
            if population is not None and eng.population is not None:
                # restore INTO the engine's store, bypassing any chaos
                # wrapper (FaultyStore) so the restore itself cannot fail
                getattr(eng.population, "inner", eng.population)._rows = (
                    population._rows
                )
            if residuals is not None and eng.residual_population is not None:
                getattr(
                    eng.residual_population, "inner", eng.residual_population
                )._rows = residuals._rows
            r = int(meta["step"])
        fleet = None
        if serve:
            # the fleet loop: serving + health + telemetry threads around
            # the SAME chunk loop (observation-only — fleet never touches
            # FedState or the traced programs)
            from repro.fleet.driver import FleetDriver

            fleet = FleetDriver(
                ckpt_dir=ckpt_dir,
                telemetry_path=telemetry_path or None,
                retain=publish_retain,
                deadline_s=round_deadline_s,
                health_port=health_port,
                meta={"algo": cfg.algo, "rounds": cfg.rounds,
                      "num_clients": cfg.num_clients,
                      "cohort_size": cfg.cohort_size,
                      "ckpt_every": ckpt_every, "resumed_at": r},
            )
            # version 1 = the params entering the run, so the serving
            # thread never decodes against unpublished (random) weights
            fleet.publish(r, state.params)
            fleet.start_serving(
                model.apply, template=state.params,
                batch_x=x_te_j[: min(128, x_te_j.shape[0])],
            )
            print(f"fleet: serving + health at {fleet.health.url} "
                  f"(telemetry: {fleet.telemetry.path})")
        while r < cfg.rounds:
            chunk = min(eval_every, cfg.rounds - r)
            if ckpt_every > 0:
                # align scan chunks to snapshot boundaries so a resumed run
                # replays the SAME chunking relative to absolute round —
                # bitwise continuation needs identical scan programs
                nxt = ckpt_every * (r // ckpt_every + 1)
                chunk = min(chunk, nxt - r)
            t0 = time.perf_counter()
            state, ms = eng.run_rounds(state, data, chunk)
            # ONE host transfer per chunk for ALL metric consumers (log +
            # telemetry + fault counters) — REP003: never per round
            host = metrics_to_host(ms)
            dt = time.perf_counter() - t0
            r += chunk
            acc = evaluate(state.params, x_te_j, y_te_j)
            pub_version = None
            snapshot = ckpt_every > 0 and (r % ckpt_every == 0 or r >= cfg.rounds)
            if snapshot:
                pop = eng.population
                res = eng.residual_population
                save_fed_run(
                    ckpt_dir, r, state,
                    population=getattr(pop, "inner", pop) if pop is not None else None,
                    residuals=getattr(res, "inner", res) if res is not None else None,
                )
                if fleet is not None:
                    pub_version = fleet.publish(r, state.params)
            if fleet is not None:
                fleet.record_chunk(start_round=r - chunk, host=host,
                                   seconds=dt, eval_acc=acc,
                                   published_version=pub_version)
            log.log(round=r, algo=cfg.algo, loss=float(host["loss"][-1]),
                    test_acc=round(acc, 4), n_active=int(host["n_active"][-1]),
                    mb_down=round(float(host["bytes_down"][-1]) / 2**20, 2),
                    mb_up=round(float(host["bytes_up"][-1]) / 2**20, 2),
                    dropped=int(host["n_dropped"].sum()) if "n_dropped" in host else None,
                    quar=int(host["n_quarantined"].sum()) if "n_quarantined" in host else None,
                    retries=int(host["n_retries"].sum()) if "n_retries" in host else None,
                    qskip=int(host["quorum_skipped"].sum()) if "quorum_skipped" in host else None,
                    seconds=dt)
            if snapshot and die_after > 0 and r >= die_after:
                # simulate preemption: no cleanup, no atexit — the
                # snapshot just published is all a resume may rely on
                # (the fleet telemetry rows above are already fsynced)
                os._exit(75)
        if fleet is not None:
            summary = fleet.stop()
            print(f"fleet: {summary.get('swaps', 0)} hot-swaps "
                  f"({summary.get('swaps_mid_session', 0)} under decode load) "
                  f"over {summary.get('steps', 0)} decode steps; "
                  f"health={summary.get('health_status')}")
        return acc, log
    for r in range(cfg.rounds):
        state, m = eng.run_round(state, data)
        if (r + 1) % eval_every == 0 or r == cfg.rounds - 1:
            host = metrics_to_host(m)  # one transfer for the whole row
            acc = evaluate(state.params, x_te_j, y_te_j)
            log.log(round=r + 1, algo=cfg.algo,
                    loss=round(float(host["loss"][-1]), 4),
                    test_acc=round(acc, 4), n_active=int(host["n_active"][-1]),
                    mb_down=round(float(host["bytes_down"][-1]) / 2**20, 2),
                    mb_up=round(float(host["bytes_up"][-1]) / 2**20, 2))
    return acc, log


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{n:.0f} B"
        n /= 1024
    return f"{n:.1f} GiB"


def list_algos_text(dim: int = 32, hidden: int = 128, n_classes: int = 10,
                    compression: "CompressionConfig | None" = None) -> str:
    """One line per registered algorithm: state-plane requirements + kernel
    routing, rendered from the registry (the same ``describe_algorithm``
    rows the kernels/README.md table is generated from), plus the §4.2
    wire cost: per-client uplink bytes/round over the spec's wire planes
    for this driver's default model (abstract shapes only — nothing is
    materialized).  ``compression`` (the resolved ``--uplink-compress``)
    reprices the column through the SAME accounting the engine bills
    (``repro.core.compress.uplink_bytes_per_client``), so the table shows
    what the configured run would actually ship."""
    from repro.core.compress import uplink_bytes_per_client

    model = mlp_classifier((dim, hidden, hidden, n_classes))
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    P = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))
    rows = []
    for n in list_algorithms():
        spec = get_algorithm(n)
        r = describe_algorithm(spec)
        up = uplink_bytes_per_client(
            compression, spec.wire_uplink_planes, P, P * 4
        )
        r["uplink bytes/round"] = f"{_fmt_bytes(up)}/client"
        rows.append(r)
    cols = ["algorithm", "local step", "server fold", "state planes",
            "uplink", "uplink bytes/round"]
    widths = {c: max(len(c), *(len(r[c]) for r in rows)) for c in cols}
    lines = ["  ".join(c.ljust(widths[c]) for c in cols)]
    lines += ["  ".join(r[c].ljust(widths[c]) for c in cols) for r in rows]
    wire = "f32 wire" if compression is None else f"{compression.kind} wire"
    lines.append(f"(P = {P:,} params: mlp {dim}-{hidden}-{hidden}-{n_classes}, {wire})")
    return "\n".join(lines)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    # choices come FROM the registry: a freshly-registered algorithm is
    # immediately runnable, and an unknown name errors with the registered
    # list (argparse renders the choices)
    ap.add_argument("--algo", default="fedcm", choices=list_algorithms())
    ap.add_argument("--list-algos", action="store_true",
                    help="print every registered algorithm (state-plane "
                         "requirements + kernel routing) and exit")
    ap.add_argument("--clients", "--num-clients", dest="clients",
                    type=int, default=100)
    ap.add_argument("--cohort", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--local-steps", type=int, default=10)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--eta-l", type=float, default=0.1)
    ap.add_argument("--eta-g", type=float, default=1.0)
    ap.add_argument("--dirichlet", type=float, default=0.6,
                    help="label-skew concentration; inf = IID")
    ap.add_argument("--participation", default="bernoulli", choices=["fixed", "bernoulli"])
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--per-round", action="store_true",
                    help="dispatch each round separately (A/B against fused scan)")
    ap.add_argument("--flat-plane", action=argparse.BooleanOptionalAction,
                    default=FedConfig.use_flat_plane,
                    help="carry the round state on the ravelled (P,) parameter "
                         "plane (--no-flat-plane keeps the per-leaf tree path)")
    ap.add_argument("--pipeline-depth", type=int, default=1,
                    help="cohorts in flight (>1 switches to the async "
                         "overlapping-cohort engine; folds are depth-1 rounds stale)")
    ap.add_argument("--staleness", type=int, default=0,
                    help="rounds of momentum staleness the clients descend "
                         "against (>0 switches to the async engine)")
    ap.add_argument("--staleness-discount", type=float, default=1.0,
                    help="FedACG-style per-round-of-staleness fold weight γ")
    ap.add_argument("--async", dest="async_pipeline", action="store_true",
                    help="force the async engine even at depth 1 / staleness 0")
    ap.add_argument("--population-store", default="resident",
                    choices=list(POPULATION_STORES),
                    help="'host' offloads per-client state to an out-of-core "
                         "host store (gather/scatter per cohort; no (N, P) "
                         "device plane) and streams client shards on demand "
                         "— the N=1e6 path")
    ap.add_argument("--availability", default="uniform",
                    choices=list(AVAILABILITY_PROCESSES),
                    help="client availability process driving cohort "
                         "sampling (uniform keeps the legacy bitwise draw)")
    ap.add_argument("--zipf-exponent", type=float, default=1.1,
                    help="skew s of the zipf availability process (w_i ∝ (i+1)^-s)")
    ap.add_argument("--dropout-rate", type=float, default=0.0,
                    help="per-round straggler probability: sampled clients "
                         "drop out of the cohort mask with this rate")
    ap.add_argument("--uplink-compress", default="none",
                    choices=["none", "int8", "bf16", "topk"],
                    help="wire-compress client uplinks (repro.core.compress): "
                         "stochastic-rounded int8 (+per-row f32 scale), "
                         "bf16, or top-k sparsification with error-feedback "
                         "residuals; 'none' keeps the f32 wire bitwise")
    ap.add_argument("--topk-frac", type=float, default=0.01,
                    help="fraction of plane coordinates top-k keeps "
                         "(only with --uplink-compress topk)")
    ap.add_argument("--cohort-shard", type=int, default=0,
                    help="shard the client axis over N devices (a "
                         "('clients',) mesh; each device runs C/N clients "
                         "end-to-end and the fold is a reduce-scatter); "
                         "0 = single-device")
    # ---- fault tolerance (ISSUE PR-7): faults are CONFIG DATA ----------
    fault = ap.add_argument_group(
        "fault injection / degradation",
        "any nonzero rate builds a FaultConfig (faults as pure config "
        "data, seeded and reproducible); quarantine of non-finite uplinks "
        "is always on when a FaultConfig is present")
    fault.add_argument("--fault-drop-rate", type=float, default=0.0,
                       help="per-client per-round uplink drop probability")
    fault.add_argument("--fault-corrupt-rate", type=float, default=0.0,
                       help="per-client per-round payload corruption probability")
    fault.add_argument("--fault-corrupt-mode", default="nan",
                       choices=["nan", "inf", "noise"],
                       help="corruption model: NaN/Inf plane fill, or scaled "
                            "bit-noise added to the delta plane")
    fault.add_argument("--fault-noise-scale", type=float, default=1.0,
                       help="noise corruption magnitude (x |leaf| stddev)")
    fault.add_argument("--fault-deadline", type=float, default=0.0,
                       help="straggler deadline (log-normal compute-time "
                            "model; >0 drops clients exceeding it)")
    fault.add_argument("--fault-store-failure-rate", type=float, default=0.0,
                       help="transient host-store gather/scatter failure "
                            "probability (engine retries with capped "
                            "exponential backoff)")
    fault.add_argument("--fault-seed", type=int, default=0,
                       help="seed of the fault PRNG chain (independent of "
                            "--seed; same seed => same fault realization)")
    fault.add_argument("--quarantine-norm-mult", type=float, default=0.0,
                       help=">0 additionally quarantines uplinks whose delta "
                            "norm exceeds mult x cohort median")
    ap.add_argument("--min-quorum", type=int, default=0,
                    help="skip the server fold (params carried unchanged) "
                         "when surviving clients fall below this count")
    ap.add_argument("--allow-empty-cohort", action="store_true",
                    help="let dropout empty the cohort entirely (the fold "
                         "degrades to a guarded no-op round) instead of the "
                         "legacy keep-first-client guard")
    # ---- preemption-safe runs ------------------------------------------
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="publish an atomic whole-run snapshot (FedState + "
                         "host population store) every N rounds; fused "
                         "path only")
    ap.add_argument("--ckpt-dir", default="",
                    help="snapshot directory (required with --ckpt-every / "
                         "--resume)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest snapshot from --ckpt-dir and "
                         "continue the trajectory bitwise")
    ap.add_argument("--die-after", type=int, default=0,
                    help="chaos: exit(75) right after the first snapshot at "
                         "round >= N (pair with --resume in a second "
                         "invocation)")
    # ---- round-to-serving fleet loop (repro.fleet) ---------------------
    fleet = ap.add_argument_group(
        "fleet serving",
        "--serve runs the round-to-serving loop: a serving thread "
        "continuously decodes against the latest published params "
        "(published at every --ckpt-every boundary, hot-swapped atomically "
        "between decode steps), /healthz-/metrics-/telemetry-tail health "
        "endpoint, append-only per-round telemetry JSONL")
    fleet.add_argument("--serve", action="store_true",
                       help="run serving + health + telemetry alongside "
                            "the fused round loop (needs --ckpt-every and "
                            "--ckpt-dir: publication rides the snapshot "
                            "cadence)")
    fleet.add_argument("--health-port", type=int, default=0,
                       help="health endpoint port (0 = ephemeral; the "
                            "bound port is printed at startup)")
    fleet.add_argument("--round-deadline", type=float, default=120.0,
                       help="/healthz liveness deadline: 503 when the last "
                            "completed round is older than this many seconds")
    fleet.add_argument("--telemetry", default="",
                       help="telemetry JSONL path (default "
                            "<ckpt-dir>/telemetry.jsonl)")
    fleet.add_argument("--publish-retain", type=int, default=4,
                       help="published model versions kept on disk (the "
                            "atomic publication ring; >= 2)")
    ap.add_argument("--dryrun", action="store_true",
                    help="resolve + persist the config artifact and exit "
                         "without training")
    return ap


def resolve_config(args: argparse.Namespace) -> FedConfig:
    """argv → FedConfig.  EVERY engine-relevant flag must be wired here —
    the dryrun artifact (and tests/test_fed_train_cli.py) assert the
    resolved values, which is what caught ``use_flat_plane`` silently
    falling back to its default."""
    # faults are pure config data: any nonzero rate materializes a
    # FaultConfig; all-defaults keeps fault=None — the engine's injection
    # code then never traces, preserving the bitwise-vs-pre-PR contract
    fault = None
    if (args.fault_drop_rate > 0.0 or args.fault_corrupt_rate > 0.0
            or args.fault_deadline > 0.0 or args.fault_store_failure_rate > 0.0
            or args.quarantine_norm_mult > 0.0):
        fault = FaultConfig(
            drop_rate=args.fault_drop_rate,
            deadline=args.fault_deadline,
            corrupt_rate=args.fault_corrupt_rate,
            corrupt_mode=args.fault_corrupt_mode,
            noise_scale=args.fault_noise_scale,
            store_failure_rate=args.fault_store_failure_rate,
            quarantine_norm_mult=args.quarantine_norm_mult,
            seed=args.fault_seed,
        )
    # compression is config data exactly like faults: "none" keeps
    # cfg.compression=None — the engine's wire-encode code then never
    # traces, preserving the bitwise-vs-pre-PR contract
    compression = None
    if args.uplink_compress != "none":
        compression = CompressionConfig(
            kind=args.uplink_compress, topk_frac=args.topk_frac,
            seed=args.seed,
        )
    return FedConfig(
        algo=args.algo, num_clients=args.clients, cohort_size=args.cohort,
        local_steps=args.local_steps, alpha=args.alpha, eta_l=args.eta_l,
        eta_g=args.eta_g, participation=args.participation, rounds=args.rounds,
        seed=args.seed, use_flat_plane=args.flat_plane,
        pipeline_depth=args.pipeline_depth, staleness=args.staleness,
        staleness_discount=args.staleness_discount,
        cohort_shard=args.cohort_shard,
        population_store=args.population_store,
        availability=args.availability,
        zipf_exponent=args.zipf_exponent,
        dropout_rate=args.dropout_rate,
        fault=fault,
        min_quorum=args.min_quorum,
        allow_empty_cohort=args.allow_empty_cohort,
        compression=compression,
    )


def _static_contracts(cfg: FedConfig, args: argparse.Namespace) -> dict:
    """One-path Layer-2 contract summary for the dryrun artifact
    (memoized inside repro.analysis.trace, so repeated in-process dryruns
    compile the tiny probe program once per path)."""
    from repro.analysis.trace import quick_contracts

    use_async = (args.async_pipeline or cfg.pipeline_depth > 1
                 or cfg.staleness > 0)
    return quick_contracts(use_async=use_async)


def write_dryrun_artifact(cfg: FedConfig, args: argparse.Namespace) -> Path:
    """Persist the RESOLVED config (not the argv) so flag-wiring is
    asserted against what the engine will actually see."""
    # the wiring contract, asserted here so a --dryrun in CI trips on
    # regressions even before any test reads the artifact back
    assert cfg.use_flat_plane == args.flat_plane
    assert cfg.pipeline_depth == args.pipeline_depth
    assert cfg.staleness == args.staleness
    assert cfg.cohort_shard == args.cohort_shard
    assert cfg.population_store == args.population_store
    assert cfg.availability == args.availability
    assert cfg.dropout_rate == args.dropout_rate
    assert cfg.min_quorum == args.min_quorum
    assert cfg.allow_empty_cohort == args.allow_empty_cohort
    if args.uplink_compress != "none":
        assert cfg.compression is not None
        assert cfg.compression.kind == args.uplink_compress
        assert cfg.compression.topk_frac == args.topk_frac
    else:
        assert cfg.compression is None
    if (args.fault_drop_rate > 0.0 or args.fault_corrupt_rate > 0.0
            or args.fault_deadline > 0.0 or args.fault_store_failure_rate > 0.0
            or args.quarantine_norm_mult > 0.0):
        assert cfg.fault is not None
        assert cfg.fault.drop_rate == args.fault_drop_rate
        assert cfg.fault.corrupt_rate == args.fault_corrupt_rate
        assert cfg.fault.corrupt_mode == args.fault_corrupt_mode
        assert cfg.fault.deadline == args.fault_deadline
        assert cfg.fault.store_failure_rate == args.fault_store_failure_rate
        assert cfg.fault.seed == args.fault_seed
    else:
        assert cfg.fault is None
    # telemetry/--dryrun agreement: every fault counter a telemetry row
    # carries must BE a RoundMetrics field (one rename breaks this loudly)
    assert set(FAULT_COUNTERS) <= set(RoundMetrics._fields), (
        set(FAULT_COUNTERS) - set(RoundMetrics._fields)
    )
    payload = {
        "resolved_config": dataclasses.asdict(cfg),
        "engine_mode": (
            "async_pipeline" if (args.async_pipeline or cfg.pipeline_depth > 1
                                 or cfg.staleness > 0)
            else ("per_round" if args.per_round else "fused_scan")
        ),
        "eval_every": args.eval_every,
        "dirichlet": args.dirichlet,
        "ckpt_every": args.ckpt_every,
        # fleet loop wiring: the serving/telemetry knobs the run would use
        "serve": {
            "enabled": args.serve,
            "health_port": args.health_port,
            "round_deadline_s": args.round_deadline,
            "telemetry_path": (args.telemetry
                               or (os.path.join(args.ckpt_dir, "telemetry.jsonl")
                                   if args.ckpt_dir else None)),
            "publish_retain": args.publish_retain,
            "publish_every": args.ckpt_every if args.serve else None,
        },
        # the telemetry row schema this build emits — asserted against
        # repro.fleet.telemetry so --dryrun and the rows a --serve run
        # writes can never disagree (RoundMetrics is the source of truth
        # for the counter names)
        "telemetry": {
            "schema": TELEMETRY_SCHEMA,
            "round_fields": list(ROUND_FIELDS),
            "fault_counters": list(FAULT_COUNTERS),
        },
        # the mesh the engine would build for cfg.cohort_shard — recorded
        # so CI (which runs dryrun single-device AND multi-device) asserts
        # the flag actually reaches the mesh constructor
        "cohort_mesh": (
            {"axes": ["clients"], "shape": [cfg.cohort_shard],
             "devices_visible": len(jax.devices())}
            if cfg.cohort_shard > 0 else None
        ),
        # Layer-2 contract state per rev (repro.analysis.trace): the
        # resolved execution path's tiny program is lowered and checked —
        # donation aliased, transfer-guard clean, exactly-once tracing
        "static_contracts": _static_contracts(cfg, args),
    }
    DRYRUN_ARTIFACT.parent.mkdir(parents=True, exist_ok=True)
    DRYRUN_ARTIFACT.write_text(json.dumps(payload, indent=1))
    return DRYRUN_ARTIFACT


def main(argv=None) -> int:
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.list_algos:
        comp = (None if args.uplink_compress == "none" else
                CompressionConfig(kind=args.uplink_compress,
                                  topk_frac=args.topk_frac, seed=args.seed))
        print(list_algos_text(compression=comp))
        return 0
    use_async = args.async_pipeline or args.pipeline_depth > 1 or args.staleness > 0
    if args.per_round and use_async:
        ap.error("--per-round dispatches one round per jit call; the async "
                 "pipelined engine is a single fused program — drop one of "
                 "--per-round / --async / --pipeline-depth / --staleness")
    if args.cohort_shard > 0 and not args.flat_plane:
        ap.error("--cohort-shard shards the flat (C, P) uplink planes — "
                 "drop --no-flat-plane")
    if args.population_store == "host" and not args.flat_plane:
        ap.error("--population-store host gathers/scatters flat (C, P) "
                 "state rows — drop --no-flat-plane")
    if args.population_store == "host" and args.cohort_shard > 0:
        ap.error("--population-store host is a single-device host loop; "
                 "it does not compose with --cohort-shard yet")
    if args.ckpt_every > 0 and use_async:
        ap.error("--ckpt-every snapshots between fused-scan chunks; the "
                 "async pipelined engine is one uninterruptible scan — "
                 "drop --async / --pipeline-depth / --staleness")
    if args.ckpt_every > 0 and args.per_round:
        ap.error("--ckpt-every rides the fused chunk loop — drop --per-round")
    if (args.ckpt_every > 0 or args.resume) and not args.ckpt_dir:
        ap.error("--ckpt-every / --resume need --ckpt-dir")
    if args.die_after > 0 and args.ckpt_every <= 0:
        ap.error("--die-after kills AFTER a snapshot — add --ckpt-every")
    if args.resume and args.ckpt_every <= 0:
        ap.error("--resume continues a snapshotted run — add --ckpt-every")
    if args.serve and args.ckpt_every <= 0:
        # (transitively this also excludes --per-round and the async
        # engine: both conflict with --ckpt-every above)
        ap.error("--serve publishes at snapshot boundaries — add "
                 "--ckpt-every N --ckpt-dir DIR")
    if args.serve and not args.ckpt_dir:
        ap.error("--serve needs --ckpt-dir (publisher + telemetry live "
                 "under it)")
    if args.publish_retain < 2:
        ap.error("--publish-retain must be >= 2: the publication ring must "
                 "outlive a reader's just-resolved version")
    cfg = resolve_config(args)
    use_compile_cache()
    if args.dryrun:
        path = write_dryrun_artifact(cfg, args)
        print(f"dryrun: resolved config written to {path}")
        return 0
    acc, _ = run_federated(cfg, args.dirichlet, eval_every=args.eval_every,
                           seed=args.seed, fused=not args.per_round,
                           async_pipeline=use_async,
                           ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir,
                           resume=args.resume, die_after=args.die_after,
                           serve=args.serve, health_port=args.health_port,
                           round_deadline_s=args.round_deadline,
                           telemetry_path=args.telemetry,
                           publish_retain=args.publish_retain)
    print(f"\n{args.algo}: final test accuracy = {acc:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
