"""Layer 2 — traced-program contract checker for the round engine.

Where :mod:`repro.analysis.lint` reads source, this module lowers the
*actual* round programs (``run_rounds`` sync/async ×
resident/store × cohort-sharded) on tiny synthetic problems and asserts
the contracts that only exist after tracing:

(a) **donation aliased** — every leaf of the donated ``FedState`` carry
    must appear as a ``tf.aliasing_output`` input attribute in the
    lowered module.  Counting attributes in the lowering (not runtime
    buffers) makes the check platform-independent: an unusable donation
    (shape-mismatched carry, accidental de-donation) drops the attribute
    at lowering time on every backend.
(b) **zero host transfers** — the compiled program executes under
    ``jax.transfer_guard("disallow")``.  On the CPU test backend this
    proves no host→device transfer happens per call (e.g. numpy batches
    re-fed every round); device→host syncs are additionally covered
    statically by lint rule REP003 (on CPU, d2h is zero-copy and the
    guard cannot observe it).
(c) **retrace budget** — each (shapes, statics) path traces exactly
    ``TRACE_BUDGET`` times, measured by the engine's own trace counters
    (resident paths) or the per-piece jit cache sizes (store paths).
    ``tests/test_run_rounds.py`` pins its trace assertions through
    :func:`assert_trace_budget`, so the budget lives here, in ONE place.
(d) **scan-carry dtype audit** — with bf16 params and default (f32)
    momentum, no sub-f32 float aval may appear in any ``lax.scan`` carry:
    the f32 master planes, not the bf16 leaf views, must be what the
    round loop advances (the PR-3 bf16-master bug class).
(e) **ordered scattered fold** — the cohort-sharded program must contain
    ``all_to_all`` (the transpose-to-columns fold) and must NOT contain
    ``psum_scatter``, which would pre-reduce per device and re-associate
    the f32 sum (breaking the bitwise oracle).

The store (host-loop) entries run the same jitted round math as the
resident entries; their host↔device boundary (store gather/scatter,
host batch generation) transfers by design, so (a)/(b) are reported as
n/a there and (c) is checked through the jit caches.

CLI: ``python -m repro.analysis.trace [--quick] [--json PATH]`` — exits
non-zero if any contract fails.  ``--quick`` runs the two-entry subset
CI uses inside the tier-1 time budget.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

TRACE_BUDGET = 1  # traces per distinct (shapes, statics) path — THE pin

_ALIAS_RE = re.compile(r"tf\.aliasing_output")
SUB_F32 = ("bf16", "bfloat16", "f16", "float16")


# ------------------------------------------------------------------ report
@dataclass
class ContractReport:
    path: str
    donation: str = "n/a"
    donation_ok: Optional[bool] = None
    transfer_guard_ok: Optional[bool] = None
    trace_count: int = -1
    trace_ok: Optional[bool] = None
    carry_dtypes_ok: Optional[bool] = None
    collectives_ok: Optional[bool] = None
    notes: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        checks = (self.donation_ok, self.transfer_guard_ok, self.trace_ok,
                  self.carry_dtypes_ok, self.collectives_ok)
        return all(c is not False for c in checks)

    def summary(self) -> str:
        def mark(v):
            return "—" if v is None else ("ok" if v else "FAIL")

        return (f"{self.path:<24} donation={mark(self.donation_ok)}"
                f"({self.donation}) guard={mark(self.transfer_guard_ok)} "
                f"traces={self.trace_count}/{TRACE_BUDGET}"
                f"[{mark(self.trace_ok)}] carry={mark(self.carry_dtypes_ok)} "
                f"collectives={mark(self.collectives_ok)}"
                + (f"  # {'; '.join(self.notes)}" if self.notes else ""))


# ------------------------------------------------------------------ helpers
def tiny_problem(algo: str = "fedcm", *, bf16: bool = False, **cfg_kw):
    """A minimal engine + data + init-state factory (mirrors the
    tests/test_run_rounds.py setup, shrunk for lowering speed)."""
    import jax

    from repro.configs.base import FedConfig
    from repro.core import FederatedEngine
    from repro.data import FederatedData, make_synthetic_classification
    from repro.models.small import classification_loss, mlp_classifier

    x, y, *_ = make_synthetic_classification(
        n_classes=4, dim=8, n_train=400, n_test=8)
    base = dict(algo=algo, num_clients=8, cohort_size=3, local_steps=2,
                participation="fixed")
    base.update(cfg_kw)
    cfg = FedConfig(**base)
    model = mlp_classifier((8, 16, 4))
    eng = FederatedEngine(cfg, classification_loss(model.apply), batch_size=8)
    data = FederatedData(x, y, cfg.num_clients, seed=0)

    def fresh():
        params = model.init(jax.random.PRNGKey(0))
        if bf16:
            import jax.numpy as jnp

            from repro.utils.trees import tree_cast
            params = tree_cast(params, jnp.bfloat16)
        return eng.init(params, jax.random.PRNGKey(1))

    return eng, data, fresh


_MAIN_SIG_RE = re.compile(r"func\.func public @main\((.*?)\)\s*->", re.S)


def donation_alias_report(lowered_text: str,
                          n_nondonated: int) -> Tuple[bool, str]:
    """(ok, summary) from a lowered module's text.

    Every argument in the lowered entry signature beyond the
    ``n_nondonated`` undonated ones must carry a ``tf.aliasing_output``
    attribute.  Donated leaves that are *dead* (e.g. the bf16 param
    views a carried f32 master plane supersedes) are pruned from the
    signature by jax before lowering — freed at donation, strictly
    better than aliased — so they are exempt by construction."""
    m = _MAIN_SIG_RE.search(lowered_text)
    if not m:
        n = len(_ALIAS_RE.findall(lowered_text))
        return n >= 1, f"aliased {n}/? (entry signature not found)"
    args = m.group(1).split("%arg")[1:]
    aliased = sum(1 for a in args if "tf.aliasing_output" in a)
    expected = len(args) - n_nondonated
    return (aliased >= expected and aliased >= 1,
            f"aliased {aliased}/{expected} "
            f"({len(args)} live args, {n_nondonated} undonated)")


def check_engine_donation(eng, state, data, n_rounds: int = 3,
                          *, mode: str = "sync") -> Tuple[bool, str]:
    """Lower the engine's donated multi-round entry point and assert every
    live leaf of the carried state is buffer-aliased to an output."""
    import jax

    if mode == "sync":
        low = eng._run_rounds.lower(
            state, data.client_x, data.client_y, n_rounds=n_rounds)
    else:
        low = eng._run_rounds_async.lower(
            state, data.client_x, data.client_y, None, None, None,
            n_rounds=n_rounds, pipeline_depth=2, staleness=0, eval_every=0,
            predict_fn=None, scan_unroll=1)
    n_nondonated = len(jax.tree_util.tree_leaves((data.client_x, data.client_y)))
    return donation_alias_report(low.as_text(), n_nondonated)


def check_transfer_guard(run: Callable[[], object]) -> Tuple[bool, str]:
    """Execute ``run`` (already compiled, device-committed inputs) under
    ``transfer_guard("disallow")``."""
    import jax

    try:
        with jax.transfer_guard("disallow"):
            jax.block_until_ready(run())
        return True, ""
    except Exception as e:  # XlaRuntimeError: Disallowed …: the finding
        return False, f"{type(e).__name__}: {e}"


def assert_trace_budget(eng, counter: str, calls: Sequence[Callable[[], object]],
                        expected_paths: Sequence[int]) -> None:
    """Run ``calls`` in order, asserting the engine's ``counter`` equals
    ``expected_paths[i] * TRACE_BUDGET`` after each — the single place the
    per-path retrace budget is pinned (tests/test_run_rounds.py and the
    contract matrix both consume it)."""
    assert getattr(eng, counter) == 0, (
        f"{counter} = {getattr(eng, counter)} before first call")
    for i, (call, paths) in enumerate(zip(calls, expected_paths)):
        call()
        got = getattr(eng, counter)
        want = paths * TRACE_BUDGET
        assert got == want, (
            f"retrace budget: {counter} = {got} after call {i}, "
            f"expected {want} ({paths} path(s) × budget {TRACE_BUDGET})")


# ------------------------------------------------------- jaxpr inspection
def iter_eqns(jaxpr):
    """All equations, recursing into sub-jaxprs (scan/cond/shard_map/…)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for u in (v if isinstance(v, (list, tuple)) else [v]):
                core = getattr(u, "jaxpr", None)
                if core is not None and hasattr(core, "eqns"):
                    yield from iter_eqns(core)
                elif hasattr(u, "eqns"):
                    yield from iter_eqns(u)


def scan_carry_violations(closed_jaxpr) -> List[str]:
    """Sub-f32 avals carried by any ``lax.scan`` in the program."""
    bad: List[str] = []
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "scan":
            continue
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        for v in eqn.invars[nc:nc + nk]:
            s = str(v.aval)
            if any(t in s for t in SUB_F32):
                bad.append(s)
    return bad


def collective_primitives(closed_jaxpr) -> set:
    return {e.primitive.name for e in iter_eqns(closed_jaxpr.jaxpr)}


def _round_jaxpr(eng, state, data, n_rounds: int = 2):
    import jax

    return jax.make_jaxpr(
        lambda s, x, y: eng._run_rounds_impl(s, x, y, n_rounds=n_rounds)
    )(state, data.client_x, data.client_y)


# ------------------------------------------------------------------ matrix
@dataclass(frozen=True)
class MatrixEntry:
    name: str
    mode: str  # "sync" | "async"
    cfg: Dict[str, object]
    algo: str = "fedcm"
    store: bool = False
    sharded: bool = False
    bf16: bool = False


MATRIX: Sequence[MatrixEntry] = (
    MatrixEntry("sync/resident", "sync", {}),
    MatrixEntry("async/resident", "async", {}),
    MatrixEntry("sync/sharded", "sync", {"cohort_shard": 1}, sharded=True),
    MatrixEntry("sync/bf16", "sync", {}, bf16=True),
    MatrixEntry("sync/store", "sync", {"population_store": "host"},
                algo="scaffold", store=True),
    MatrixEntry("async/store", "async", {"population_store": "host"},
                algo="scaffold", store=True),
)

# the fast subset CI's static-analysis job runs inside the tier-1 budget
QUICK = ("sync/resident", "async/resident")


def _check_resident(entry: MatrixEntry) -> ContractReport:
    import jax

    rep = ContractReport(entry.name)
    eng, data, fresh = tiny_problem(entry.algo, bf16=entry.bf16, **entry.cfg)
    n = 3
    counter = ("run_rounds_traces" if entry.mode == "sync"
               else "run_rounds_async_traces")
    if entry.mode == "sync":
        def call():
            return eng.run_rounds(fresh(), data, n)
    else:
        def call():
            return eng.run_rounds_async(fresh(), data, n + 1,
                                        pipeline_depth=2, drain=False)

    # (c) retrace budget: two identical calls, one trace
    try:
        assert_trace_budget(eng, counter, [call, call], [1, 1])
        rep.trace_ok = True
    except AssertionError as e:
        rep.trace_ok = False
        rep.notes.append(str(e))
    rep.trace_count = getattr(eng, counter)

    # (b) compiled execution under transfer_guard (fresh state: the prior
    # calls donated theirs)
    st = fresh()
    if entry.mode == "sync":
        def guarded():
            return eng._run_rounds(st, data.client_x, data.client_y, n_rounds=n)
    else:
        def guarded():
            return eng._run_rounds_async(
                st, data.client_x, data.client_y, None, None, None,
                n_rounds=n + 1, pipeline_depth=2, staleness=0, eval_every=0,
                predict_fn=None, scan_unroll=1)
    rep.transfer_guard_ok, why = check_transfer_guard(guarded)
    if why:
        rep.notes.append(why)

    # (a) donation aliasing from the lowered module
    rep.donation_ok, rep.donation = check_engine_donation(
        eng, fresh(), data, n, mode=entry.mode)

    # (d)/(e) jaxpr audits on the sync path (the async program shares the
    # round body; the bf16 entry exists exactly for (d))
    if entry.mode == "sync":
        jx = _round_jaxpr(eng, fresh(), data)
        bad = scan_carry_violations(jx)
        rep.carry_dtypes_ok = not bad
        if bad:
            rep.notes.append(f"sub-f32 scan carries: {bad[:4]}")
        if entry.sharded:
            prims = collective_primitives(jx)
            rep.collectives_ok = ("all_to_all" in prims
                                  and "psum_scatter" not in prims)
            if not rep.collectives_ok:
                rep.notes.append(f"collectives seen: "
                                 f"{sorted(p for p in prims if 'all' in p or 'psum' in p)}")
    return rep


def _check_store(entry: MatrixEntry) -> ContractReport:
    import jax

    rep = ContractReport(entry.name)
    rep.donation = "n/a (host-loop store path)"
    rep.notes.append("store boundary transfers by design; device round "
                     "math is the resident entries' (shared jits)")
    eng, data, fresh = tiny_problem(entry.algo, **entry.cfg)
    n = 2
    if entry.mode == "sync":
        def call(st):
            return eng.run_rounds(st, data, n)
    else:
        def call(st):
            return eng.run_rounds_async(st, data, n, pipeline_depth=2)

    st, _ = call(fresh())
    st, _ = call(st)
    # (c) via the per-FlatSpec jit cache: every piece the loop used traced
    # at most TRACE_BUDGET times across both calls
    caches = {
        name: jit._cache_size()
        for jits in getattr(eng, "_store_jit_cache", {}).values()
        for name, jit in jits.items()
    }
    used = {k: v for k, v in caches.items() if v > 0}
    rep.trace_count = max(caches.values(), default=-1)
    rep.trace_ok = bool(used) and all(v <= TRACE_BUDGET for v in caches.values())
    if not rep.trace_ok:
        rep.notes.append(f"store jit cache sizes: {caches}")
    return rep


def run_matrix(quick: bool = False,
               entries: Optional[Sequence[MatrixEntry]] = None) -> List[ContractReport]:
    todo = entries if entries is not None else [
        e for e in MATRIX if not quick or e.name in QUICK]
    return [(_check_store if e.store else _check_resident)(e) for e in todo]


def quick_contracts(*, use_async: bool = False) -> Dict[str, object]:
    """One-path contract summary for the ``fed_train --dryrun`` artifact.

    Memoized per path: dry-runs in one process (the CLI test suite) pay
    the tiny compile once."""
    name = f"{'async' if use_async else 'sync'}/resident"
    if name not in _QUICK_CACHE:
        entry = next(e for e in MATRIX if e.name == name)
        rep = _check_resident(entry)
        _QUICK_CACHE[name] = {
            "path": name,
            "donation_ok": bool(rep.donation_ok),
            "donation": rep.donation,
            "transfer_guard_ok": bool(rep.transfer_guard_ok),
            "trace_count": rep.trace_count,
            "trace_budget": TRACE_BUDGET,
        }
    return dict(_QUICK_CACHE[name])


_QUICK_CACHE: Dict[str, Dict[str, object]] = {}


# ------------------------------------------------------------------ CLI
def _main(argv=None) -> int:
    from repro.analysis import trace as canonical

    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.trace",
        description="Traced-program contract checker (Layer 2).")
    ap.add_argument("--quick", action="store_true",
                    help="fast two-entry subset (CI tier-1 budget)")
    ap.add_argument("--json", type=__import__("pathlib").Path, default=None,
                    help="dump the reports as JSON (CI artifact)")
    args = ap.parse_args(argv)

    reports = canonical.run_matrix(quick=args.quick)
    for r in reports:
        print(r.summary())
    if args.json:
        args.json.parent.mkdir(parents=True, exist_ok=True)
        args.json.write_text(json.dumps(
            [vars(r) for r in reports], indent=2, default=str) + "\n")
    bad = [r for r in reports if not r.ok]
    print(f"repro.analysis.trace: {len(reports) - len(bad)}/{len(reports)} "
          f"contract entries clean")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(_main())
