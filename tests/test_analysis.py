"""Tests for repro.analysis — Layer-1 lint fixtures + Layer-2 contracts.

Each REP rule gets at least one failing, one passing, and one suppressed
fixture, run through ``lint_sources`` with virtual repo paths so scope
rules (REP001's core/kernels/sharding gate, REP002's compat exemption)
are exercised too.  The Layer-2 tests prove the checkers *detect*
violations (deliberately broken donation, a bf16 scan carry), not just
that the shipped engine passes them.
"""

import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.analysis.lint import (
    REP001,
    REP002,
    REP003,
    REP004,
    REP005,
    lint_repo,
    lint_sources,
    load_baseline,
    sync_readme,
)
from repro.analysis.trace import (
    TRACE_BUDGET,
    collective_primitives,
    donation_alias_report,
    quick_contracts,
    scan_carry_violations,
)

CORE = "src/repro/core/fixture.py"
LAUNCH = "src/repro/launch/fixture.py"


def rules_of(findings):
    return sorted({f.rule for f in findings})


def run_rule(rule_cls, src, path=CORE):
    return lint_sources({path: src}, rules=[rule_cls()])


# --------------------------------------------------------------- REP001
REP001_BAD = """
def pick(cfg):
    if cfg.algo == "fedcm":
        return 1
    return 0
"""

REP001_OK = """
def pick(cfg, registry):
    spec = registry.get(cfg.algo)
    return spec.local_update
"""


def test_rep001_flags_name_keyed_branch():
    assert rules_of(run_rule(REP001, REP001_BAD)) == ["REP001"]


def test_rep001_passes_registry_dispatch():
    assert run_rule(REP001, REP001_OK) == []


def test_rep001_scope_is_core_kernels_sharding_only():
    assert run_rule(REP001, REP001_BAD, path=LAUNCH) == []


def test_rep001_suppressed_with_reason():
    src = REP001_BAD.replace(
        '== "fedcm":', '== "fedcm":  # repro: noqa REP001 -- legacy shim')
    assert run_rule(REP001, src) == []


def test_reasonless_noqa_is_ignored():
    src = REP001_BAD.replace('== "fedcm":', '== "fedcm":  # repro: noqa REP001')
    assert rules_of(run_rule(REP001, src)) == ["REP001"]


# --------------------------------------------------------------- REP002
REP002_BAD = """
import jax
from jax.sharding import Mesh

def build(devs):
    jax.make_mesh((1,), ("x",))
    return Mesh(devs, ("clients",))
"""

REP002_OK = """
from repro.utils.compat import device_mesh, make_mesh

def build(devs):
    make_mesh((1,), ("x",))
    return device_mesh(devs, ("clients",))
"""


def test_rep002_flags_direct_mesh_apis():
    found = run_rule(REP002, REP002_BAD, path=LAUNCH)
    assert len(found) == 2 and rules_of(found) == ["REP002"]
    assert "compat" in found[0].message


def test_rep002_passes_compat_routed():
    assert run_rule(REP002, REP002_OK, path=LAUNCH) == []


def test_rep002_exempts_compat_module_itself():
    assert run_rule(REP002, REP002_BAD, path="src/repro/utils/compat.py") == []


def test_rep002_suppressed_with_reason():
    src = REP002_BAD.replace(
        '("x",))', '("x",))  # repro: noqa REP002 -- version probe').replace(
        '("clients",))', '("clients",))  # repro: noqa REP002 -- version probe')
    assert run_rule(REP002, src, path=LAUNCH) == []


# --------------------------------------------------------------- REP003
REP003_BAD = """
import jax

@jax.jit
def step(x):
    return float(x)
"""

# reachability: the sync lives in a helper the jitted root calls
REP003_BAD_INDIRECT = """
import jax

def helper(x):
    return x.item()

@jax.jit
def step(x):
    return helper(x)
"""

REP003_OK = """
import jax

@jax.jit
def step(x, cfg):
    return x * float(cfg.lr)

def host_side(x):
    return float(x)
"""


def test_rep003_flags_host_sync_in_jit():
    assert rules_of(run_rule(REP003, REP003_BAD)) == ["REP003"]


def test_rep003_resolves_call_graph():
    found = run_rule(REP003, REP003_BAD_INDIRECT)
    assert rules_of(found) == ["REP003"] and ".item()" in found[0].message


def test_rep003_static_config_and_host_code_pass():
    # float(cfg.lr) is static at trace time; host_side is unreachable
    assert run_rule(REP003, REP003_OK) == []


def test_rep003_suppressed_with_reason():
    src = REP003_BAD.replace(
        "float(x)", "float(x)  # repro: noqa REP003 -- fixture")
    assert run_rule(REP003, src) == []


# --------------------------------------------------------------- REP004
REP004_BAD = """
import jax

def f(key):
    a = jax.random.normal(key)
    b = jax.random.normal(key)
    return a + b
"""

REP004_BAD_RAW = """
import jax

def f(state):
    return jax.random.normal(state.rng)
"""

REP004_OK = """
import jax

def f(key):
    k1, k2 = jax.random.split(key)
    a = jax.random.normal(k1)
    b = jax.random.normal(k2)
    return a + b
"""


def test_rep004_flags_key_reuse():
    found = run_rule(REP004, REP004_BAD)
    assert rules_of(found) == ["REP004"] and "more than one" in found[0].message


def test_rep004_flags_stored_raw_key():
    found = run_rule(REP004, REP004_BAD_RAW)
    assert rules_of(found) == ["REP004"] and "state.rng" in found[0].message


def test_rep004_passes_split_discipline():
    assert run_rule(REP004, REP004_OK) == []


def test_rep004_suppressed_with_reason():
    src = REP004_BAD.replace(
        "b = jax.random.normal(key)",
        "b = jax.random.normal(key)  # repro: noqa REP004 -- fixture")
    assert run_rule(REP004, src) == []


# --------------------------------------------------------------- REP005
REP005_BAD = """
import jax.numpy as jnp

def fold(x):
    return jnp.sum(x.astype(jnp.bfloat16))
"""

REP005_OK = """
import jax.numpy as jnp

def fold(x, w):
    a = jnp.sum(x.astype(jnp.bfloat16), dtype=jnp.float32)
    b = jnp.mean(x.astype(jnp.bfloat16)).astype(jnp.float32)
    c = jnp.sum(x)                       # no sub-f32 cast involved
    d = jnp.einsum("i,i->", x, w.astype(x.dtype))  # alignment cast
    return a + b + c + d
"""


def test_rep005_flags_subf32_reduction():
    assert rules_of(run_rule(REP005, REP005_BAD)) == ["REP005"]


def test_rep005_passes_mitigated_and_aligned():
    assert run_rule(REP005, REP005_OK) == []


def test_rep005_suppressed_with_reason():
    src = REP005_BAD.replace(
        "bfloat16))", "bfloat16))  # repro: noqa REP005 -- fixture")
    assert run_rule(REP005, src) == []


# ----------------------------------------------------- repo + README gates
def test_repo_is_lint_clean_modulo_baseline():
    base = load_baseline()
    fresh = [f for f in lint_repo() if f.baseline_key not in base]
    assert fresh == [], "\n".join(str(f) for f in fresh)


def test_readme_rule_table_in_sync():
    assert sync_readme(write=False), (
        "src/repro/analysis/README.md rule table is stale — regenerate "
        "with `python -m repro.analysis.lint --write`")


# ------------------------------------------------------- Layer-2 contracts
def test_donation_break_is_detected():
    def step(state, x):
        return state + x

    s, x = jnp.zeros(4), jnp.ones(4)
    good = jax.jit(step, donate_argnums=(0,)).lower(s, x).as_text()
    bad = jax.jit(step).lower(s, x).as_text()
    ok, _ = donation_alias_report(good, n_nondonated=1)
    assert ok
    broken, summary = donation_alias_report(bad, n_nondonated=1)
    assert not broken and "aliased 0/1" in summary


def test_scan_carry_dtype_audit_detects_subf32():
    def scanned(c, xs):
        return lax.scan(lambda c, x: (c + x, x), c, xs)

    bf = jax.make_jaxpr(scanned)(
        jnp.zeros(3, jnp.bfloat16), jnp.zeros((4, 3), jnp.bfloat16))
    f32 = jax.make_jaxpr(scanned)(
        jnp.zeros(3, jnp.float32), jnp.zeros((4, 3), jnp.float32))
    assert scan_carry_violations(bf)
    assert scan_carry_violations(f32) == []


def test_collective_audit_sees_primitives():
    if jax.device_count() < 2:
        mapped = jax.make_jaxpr(lambda x: x)(jnp.zeros(2))
        assert "psum_scatter" not in collective_primitives(mapped)
        return
    from repro.utils.compat import make_mesh, shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh((2,), ("c",))
    f = shard_map(lambda x: lax.psum_scatter(x, "c"), mesh=mesh,
                  in_specs=P("c"), out_specs=P("c"))
    prims = collective_primitives(jax.make_jaxpr(f)(jnp.zeros((2, 2))))
    assert any("psum" in p for p in prims)


def test_quick_contracts_pass_on_shipped_engine():
    sc = quick_contracts(use_async=False)
    assert sc["donation_ok"] and sc["transfer_guard_ok"]
    assert sc["trace_count"] == sc["trace_budget"] == TRACE_BUDGET
