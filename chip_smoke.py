"""Smoke run of the federated round on a TPU, through the entry points a
user calls.  Weights and data are random, made from ``--seed``.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # the cohort-sharded path on four chips

Phase A, ``fed_train``'s main path at the paper's Setting I
(``configs/fedcm_paper.py``): 100 clients, 10% Bernoulli participation,
fedcm (α = 0.1, η_l = 0.1), Dirichlet(0.6), K = 50 local steps, 20 rounds as
two fused chunks of 10.  On the f32 wire ``run_federated`` runs the flat
engine (the Pallas kernels) against the tree oracle (``use_flat_plane=False``,
plain jnp).  The int8 wire (the dequant fold) exists on the flat engine
only, so there the compiled kernels (Mosaic) run against the same run with
``repro.kernels.interpret_mode`` patched to the Pallas interpreter.

Phase B, a real-width LM client through ``FederatedEngine.run_rounds``:
llama3.2-1b at its published widths (d_model 2048, 32 Q / 8 KV heads of 64,
gated-SiLU d_ff 8192), depth cut to 2 layers and the vocabulary to an
eighth (16,032); fedcm, 16 clients, cohort 2, K = 2, B = 4, S = 512,
3 rounds, the flat engine against the tree oracle.

``--chips 4`` runs only the sharded path and its reference: Phase A with
``--cohort-shard 4`` against the same run unsharded on one chip (server
state held to f32-bitwise), and Phase B at cohort 4, one client per chip, for 2 rounds.

The lines before the last are smoke readings, not benchmark metrics:
seconds spent in XLA compilation, steady seconds per round (timed up to
the round's outputs on the host), the compiler's memory analysis of the
LM round program, the process's peak device bytes so far (a running
maximum over every phase run before it, not one phase's peak), and
whether the compiled round holds a Pallas kernel (``tpu_custom_call``).
Every reading comes from the run whose losses are compared.  The last
line is the JSON result, printed only on a TPU after every check passed;
otherwise the exit code is 1.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from unittest import mock

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np

import jax

from repro.configs.base import CompressionConfig, FedConfig, get_config
from repro import kernels
from repro.configs.fedcm_paper import DIRICHLET_ALPHA, SETTING_I
from repro.core import FederatedEngine
from repro.data import FederatedTokens, make_federated_lm_corpus
from repro.checkpoint import load_flat
from repro.launch.fed_train import run_federated
from repro.models import build_model, federated_lm_loss
from repro.utils.compile_cache import use_compile_cache

# The flat engine against the tree oracle, or Mosaic against the Pallas
# interpreter.  The two differ only in how the f32 update arithmetic is
# rounded and contracted.
RTOL_F32_CLIENT = 1e-3  # Phase A: Setting I's losses are ~1e-5, so one
#                         rounding step in the update moves them ~5e-4
RTOL_LM_CLIENT = 1e-5  # Phase B: the chip measured 0.0
RTOL_SHARDED_LOSS = 1e-5  # four chips: same state, loss reduced per device

# jax.monitoring event that times each XLA compilation
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

PHASE_A = dataclasses.replace(SETTING_I, rounds=20)
PHASE_A_CHUNK = 10

LM_ARCH = "llama3.2-1b"
LM_LAYERS = 2
LM_CLIENTS, LM_K, LM_BATCH, LM_SEQ = 16, 2, 4, 512
LM_SEQS_PER_CLIENT = 32


def lm_client_config(layers: int = LM_LAYERS):
    """llama3.2-1b at its published widths; depth and vocabulary cut."""
    cfg = get_config(LM_ARCH)
    return dataclasses.replace(cfg, n_layers=layers, vocab_size=cfg.vocab_size // 8)


def process_peak_bytes():
    """Peak bytes in use on each device since the process started: a
    running maximum over every phase so far, not one program's peak."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]


def memory_analysis(compiled):
    """The compiler's byte counts for one program (``None`` where the
    backend gives none)."""
    m = compiled.memory_analysis()
    if m is None:
        return None
    return {k: getattr(m, f"{k}_size_in_bytes")
            for k in ("argument", "output", "alias", "temp", "generated_code")}


def round_program(eng: FederatedEngine, n_rounds: int):
    """``eng.run_rounds`` over ``n_rounds`` as one jitted function of
    (state, client_x, client_y), for ahead-of-time compilation."""

    def run(state, client_x, client_y):
        data = SimpleNamespace(client_x=client_x, client_y=client_y)
        return eng.run_rounds(state, data, n_rounds)

    return jax.jit(run, donate_argnums=0)


def time_rounds(eng, state, data, n_rounds):
    """Compile ``n_rounds`` of ``eng``, run them twice from ``state``.

    Returns (readings, metrics of the first run).  The second run times
    the steady state: the compiled program, no compilation."""
    args = (data.client_x, data.client_y)
    t0 = time.perf_counter()
    compiled = round_program(eng, n_rounds).lower(state, *args).compile()
    compile_s = time.perf_counter() - t0
    state, ms = compiled(state, *args)
    jax.block_until_ready((state, ms))
    t0 = time.perf_counter()
    state, _ = compiled(state, *args)
    jax.block_until_ready(state)
    steady = (time.perf_counter() - t0) / n_rounds
    readings = {
        "compile_s": compile_s,
        "steady_s_per_round": steady,
        "memory_analysis": memory_analysis(compiled),
        "process_peak_bytes_in_use": process_peak_bytes(),
        "tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
    }
    return readings, ms


def observed_federated_run(cfg, *, chunk, seed, ckpt_dir="", **data_kw):
    """``run_federated`` over ``cfg.rounds`` in chunks of ``chunk``, with
    readings taken from that run: XLA compile seconds (``jax.monitoring``),
    the steady seconds per round of its last chunk, and whether the round
    program it compiled holds a Pallas kernel (its StableHLO as handed to
    the compiler, dumped through ``jax_dump_ir_to``).

    Returns (readings, test accuracy, chunk losses)."""
    compile_s = []

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            compile_s.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    with tempfile.TemporaryDirectory() as ir_dir:
        jax.config.update("jax_dump_ir_to", ir_dir)
        try:
            acc, log = run_federated(
                cfg, DIRICHLET_ALPHA, eval_every=chunk, seed=seed, echo=False,
                ckpt_every=chunk if ckpt_dir else 0, ckpt_dir=ckpt_dir, **data_kw)
        finally:
            jax.config.update("jax_dump_ir_to", "")
            jax.monitoring.unregister_event_duration_listener(on_duration)
        round_ir = [f.read_text()
                    for f in Path(ir_dir).glob("*_run_rounds_impl_compile.mlir")]
    if not round_ir:
        raise RuntimeError("run_federated compiled no round program")
    readings = {
        "compile_s": sum(compile_s),
        "steady_s_per_round": log.column("seconds")[-1] / chunk,
        "process_peak_bytes_in_use": process_peak_bytes(),
        "tpu_custom_call": all("tpu_custom_call" in ir for ir in round_ir),
    }
    return readings, acc, log.column("loss")


def say(label, **readings):
    print(f"smoke {label}: " + " ".join(f"{k}={v}" for k, v in readings.items()),
          flush=True)


def compare(label, got, want, rtol, failures):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    rel = float(np.max(np.abs(got - want) / np.abs(want)))
    say(label, max_rel_diff=rel, rtol=rtol)
    if not (np.all(np.isfinite(got)) and np.all(np.isfinite(want))):
        failures.append(f"{label}: non-finite loss")
    elif rel > rtol:
        failures.append(f"{label}: losses differ by {rel:g} > rtol {rtol:g}")


def phase_a(failures, *, base=PHASE_A, chunk=PHASE_A_CHUNK, seed=0, **data_kw):
    """Setting I through ``run_federated``: on the f32 wire the flat engine
    against the tree oracle; on the int8 wire, which only the flat engine
    speaks, Mosaic against the Pallas interpreter."""
    base = dataclasses.replace(base, seed=seed)

    def run(label, cfg, compiled):
        readings, acc, losses = observed_federated_run(
            cfg, chunk=chunk, seed=seed, **data_kw)
        say(label, **readings, chunk_losses=losses, test_acc=acc)
        if compiled and not readings["tpu_custom_call"]:
            failures.append(f"{label}: no Pallas kernel in the compiled round")
        return losses

    kernel = run("A[f32,kernel]", base, True)
    tree = run("A[f32,tree]", dataclasses.replace(base, use_flat_plane=False), False)
    compare("A[f32] kernel vs tree", kernel, tree, RTOL_F32_CLIENT, failures)
    int8 = dataclasses.replace(base, compression=CompressionConfig(kind="int8", seed=seed))
    mosaic = run("A[int8,mosaic]", int8, True)
    with mock.patch.object(kernels, "interpret_mode", lambda: True):
        interpreted = run("A[int8,interpreter]", int8, False)
    compare("A[int8] Mosaic vs interpreter", mosaic, interpreted, RTOL_F32_CLIENT,
            failures)


def phase_a_sharded(failures, *, shards=4, base=PHASE_A, chunk=PHASE_A_CHUNK,
                    seed=0, **data_kw):
    """Setting I on the flat engine with the cohort sharded over ``shards``
    chips, against the same run unsharded on one chip.  The run state
    (``fed_train``'s last snapshot) is held to f32-bitwise.  The loss
    metric is held to ``RTOL_SHARDED_LOSS``: each client's loss is reduced
    at a different vmap width per device, and XLA may associate that
    reduce differently (1-ulp differences on 8 CPU devices, with the
    state bitwise)."""
    base = dataclasses.replace(base, seed=seed)
    out = {}
    for n in (shards, 0):
        cfg = dataclasses.replace(base, cohort_shard=n)
        label = f"A[cohort_shard={n}]"
        with tempfile.TemporaryDirectory() as ckpt_dir:
            readings, acc, losses = observed_federated_run(
                cfg, chunk=chunk, seed=seed, ckpt_dir=ckpt_dir, **data_kw)
            snapshot, _ = load_flat(ckpt_dir)
        out[n] = ({k: np.asarray(v) for k, v in snapshot.items()}, losses)
        say(label, **readings, chunk_losses=losses, test_acc=acc)
        if not readings["tpu_custom_call"]:
            failures.append(f"{label}: no Pallas kernel in the compiled round")
    (st_s, loss_s), (st_u, loss_u) = out[shards], out[0]
    if st_s.keys() != st_u.keys():
        failures.append(f"A sharded vs unsharded: state leaves differ "
                        f"({sorted(st_s.keys() ^ st_u.keys())})")
    keys = sorted(st_s.keys() & st_u.keys())
    diff = max(float(np.max(np.abs(st_s[k].astype(np.float64)
                                   - st_u[k].astype(np.float64)))) for k in keys)
    say("A sharded vs unsharded state", leaves=len(keys), max_abs_diff=diff)
    if not all(np.array_equal(st_s[k], st_u[k]) for k in keys):
        failures.append(f"A sharded vs unsharded: state not bitwise "
                        f"(max |Δ| {diff:g})")
    compare("A sharded vs unsharded loss", loss_s, loss_u, RTOL_SHARDED_LOSS,
            failures)


def lm_engine(mcfg, *, cohort, flat=True, shards=0, rounds, seed):
    model = build_model(mcfg)
    cfg = FedConfig(algo="fedcm", num_clients=LM_CLIENTS, cohort_size=cohort,
                    participation="fixed", local_steps=LM_K, alpha=0.1,
                    eta_l=0.1, rounds=rounds, seed=seed,
                    use_flat_plane=flat, cohort_shard=shards)
    eng = FederatedEngine(cfg, federated_lm_loss(model), batch_size=LM_BATCH)
    return eng, model


def lm_data(mcfg, seed, seq=LM_SEQ):
    return FederatedTokens.from_sequences(make_federated_lm_corpus(
        mcfg.vocab_size, LM_CLIENTS, LM_SEQS_PER_CLIENT, seq + 1, seed=seed))


def phase_b(failures, *, mcfg=None, cohort=2, rounds=3, seed=0, seq=LM_SEQ):
    """The real-width LM client, the flat engine against the tree oracle."""
    mcfg = mcfg or lm_client_config()
    data = lm_data(mcfg, seed, seq)
    losses = {}
    for kernel in (True, False):
        label = f"B[{'kernel' if kernel else 'tree'}]"
        eng, model = lm_engine(mcfg, cohort=cohort, flat=kernel,
                               rounds=rounds, seed=seed)
        state = eng.init(model.init(jax.random.PRNGKey(seed)),
                         jax.random.PRNGKey(seed + 1))
        readings, ms = time_rounds(eng, state, data, rounds)
        del state
        losses[kernel] = np.asarray(ms.loss)
        say(label, arch=mcfg.name, layers=mcfg.n_layers, vocab=mcfg.vocab_size,
            cohort=cohort, local_steps=LM_K, batch=LM_BATCH, seq=seq,
            **readings, round_losses=losses[kernel].tolist())
        if kernel and not readings["tpu_custom_call"]:
            failures.append(f"{label}: no Pallas kernel in the compiled round")
        if not np.all(np.isfinite(losses[kernel])):
            failures.append(f"{label}: non-finite loss")
        elif not losses[kernel][-1] < losses[kernel][0]:
            failures.append(f"{label}: loss did not fall "
                            f"({losses[kernel][0]:g} -> {losses[kernel][-1]:g})")
    compare("B kernel vs tree", losses[True], losses[False], RTOL_LM_CLIENT,
            failures)


def phase_b_sharded(failures, *, shards=4, mcfg=None, rounds=2, seed=0,
                    seq=LM_SEQ):
    """The LM client at cohort ``shards``, one client per chip.  An
    unsharded round at that cohort does not fit one chip, so there is no
    reference: the losses must be finite."""
    mcfg = mcfg or lm_client_config()
    eng, model = lm_engine(mcfg, cohort=shards, shards=shards,
                           rounds=rounds, seed=seed)
    state = eng.init(model.init(jax.random.PRNGKey(seed)),
                     jax.random.PRNGKey(seed + 1))
    readings, ms = time_rounds(eng, state, lm_data(mcfg, seed, seq), rounds)
    losses = np.asarray(ms.loss)
    say(f"B[cohort_shard={shards}]", arch=mcfg.name, layers=mcfg.n_layers,
        cohort=shards, **readings, round_losses=losses.tolist())
    if not readings["tpu_custom_call"]:
        failures.append("B sharded: no Pallas kernel in the compiled round")
    if not np.all(np.isfinite(losses)):
        failures.append("B sharded: non-finite loss")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cohort-sharded path and its reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} visible",
              file=sys.stderr)
        return 1
    cache = Path(use_compile_cache())
    say("device", platform=dev.platform, kind=dev.device_kind, count=len(devices),
        compile_cache=cache, cache_entries_at_start=(
            sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0))

    failures = []
    if args.chips == 1:
        phase_a(failures, seed=args.seed)
        phase_b(failures, seed=args.seed)
    else:
        phase_a_sharded(failures, shards=args.chips, seed=args.seed)
        phase_b_sharded(failures, shards=args.chips, seed=args.seed)
    for f in failures:
        print(f"chip_smoke FAILED: {f}", file=sys.stderr)
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
