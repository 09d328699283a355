"""The round program's named scopes reach its compiled metadata.

A device trace attributes the round program's time to layers by three
``jax.named_scope`` names in each operation's ``op_name``: the plane
views (``FlatSpec.ravel``/``unravel``, and the gradient's return into the
plane, which is ``unravel``'s backward: one concatenate of the leaf
gradients), each client's loss, gradient and finalize, and the round
close with the round's metric norms.  The innermost scope
wins: an operation counts to the first of ``ORDER`` its own ``op_name``
holds.  These tests compile the flat round on the kernel path (Pallas in
interpret mode) at a tiny size and read the ``op_name``s from the
optimized HLO text.
"""
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

import jax

from repro.configs.base import FedConfig
from repro.core import FederatedEngine
from repro.data import FederatedData, make_synthetic_classification
from repro.models.small import classification_loss, mlp_classifier

PLANE_VIEW, LOCAL_STEPS, FOLD = "fedcm.plane_view", "fedcm.local_steps", "fedcm.fold"
ORDER = (PLANE_VIEW, LOCAL_STEPS, FOLD)
ROOT = Path(__file__).resolve().parents[1]

_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?)([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def instructions(hlo_text):
    """(name, opcode, shape text, op_name) of every instruction, fused
    bodies included."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            op = _OP_NAME.search(line)
            out.append((m.group(1), m.group(3), m.group(2).strip(), op.group(1) if op else ""))
    return out


def scope_of(op_name):
    return next((s for s in ORDER if s in op_name), None)


_DATA = {}


def _engine(**kw):
    if not _DATA:
        x, y, *_ = make_synthetic_classification(n_classes=4, dim=8, n_train=640, n_test=8)
        _DATA["d"] = FederatedData(x, y, 8, seed=0)
        _DATA["model"] = mlp_classifier((8, 16, 4))
    model = _DATA["model"]
    cfg = FedConfig(**{"algo": "fedcm", "num_clients": 8, "cohort_size": 4, "local_steps": 2,
                       "weight_decay": 1e-3, **kw})
    eng = FederatedEngine(cfg, classification_loss(model.apply), batch_size=8)
    state = eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))
    d = _DATA["d"]
    return eng, state, jax.numpy.asarray(d.client_x), jax.numpy.asarray(d.client_y)


def _compiled_text(kind):
    eng, st, cx, cy = _engine()
    if kind == "sync":
        lowered = eng._run_rounds.lower(st, cx, cy, n_rounds=2)
    else:
        lowered = eng._run_rounds_async.lower(
            st, cx, cy, None, None, None, n_rounds=3, pipeline_depth=2, staleness=1,
            eval_every=0, predict_fn=None)
    return lowered.compile().as_text()


@pytest.fixture(scope="module", params=["sync", "async"])
def program(request):
    return instructions(_compiled_text(request.param))


def test_every_scope_reaches_the_round_program(program):
    seen = {scope_of(op_name) for _, _, _, op_name in program}
    assert set(ORDER) <= seen


def test_gradient_returns_to_the_plane_under_plane_view(program):
    """The gradient returns to the plane as one concatenate of the leaf
    gradients into a ``(C, P')`` plane, under the plane view; no op of
    the plane view in the local steps pads a leaf to the plane."""
    eng, st, *_ = _engine()
    plane = f",{eng._flat_spec(st.params).plane_size}]"
    back = [(opcode, shape, op_name) for _, opcode, shape, op_name in program
            if LOCAL_STEPS in op_name and "transpose(" in op_name and PLANE_VIEW in op_name]
    assert any(opcode == "concatenate" and plane in shape for opcode, shape, _ in back), back
    assert all(scope_of(n) == PLANE_VIEW for _, _, n in back)
    view_ops = {opcode for _, opcode, _, op_name in program
                if LOCAL_STEPS in op_name and scope_of(op_name) == PLANE_VIEW}
    assert view_ops and "pad" not in view_ops, view_ops


def test_local_step_matmuls_count_to_local_steps(program):
    dots = [op_name for _, _, _, op_name in program
            if op_name.endswith("/dot_general") and "jvp(" in op_name]
    assert dots
    assert {scope_of(n) for n in dots} == {LOCAL_STEPS}, dots


def test_client_finalize_and_metric_norms_have_their_layer(program):
    finalize = [op_name for _, opcode, _, op_name in program
                if opcode == "subtract" and op_name.endswith(f"vmap({LOCAL_STEPS})/sub")]
    assert finalize
    norms = [op_name for _, opcode, _, op_name in program if opcode == "sqrt"]
    assert norms and {scope_of(n) for n in norms} == {FOLD}, norms


def test_mimelite_full_gradient_counts_to_local_steps():
    eng, st, cx, cy = _engine(algo="mimelite")
    program = instructions(eng._run_rounds.lower(st, cx, cy, n_rounds=1).compile().as_text())
    full = [op_name for _, _, _, op_name in program
            if op_name.endswith("/dot_general") and f"vmap({LOCAL_STEPS})" in op_name]
    assert full and {scope_of(n) for n in full} == {LOCAL_STEPS}


_SHARDED = textwrap.dedent("""
    import json, sys
    import jax
    sys.path[:0] = [sys.argv[1]]
    from test_named_scopes import _engine, instructions
    from repro.launch.mesh import make_cohort_mesh
    from repro.core import FederatedEngine

    eng, st, cx, cy = _engine()
    eng = FederatedEngine(eng.cfg, eng.loss_fn, batch_size=8, cohort_mesh=make_cohort_mesh(4))
    st = eng.init(st.params, jax.random.PRNGKey(1))
    out = {
        "sync": eng._run_rounds.lower(st, cx, cy, n_rounds=2),
        "async": eng._run_rounds_async.lower(
            st, cx, cy, None, None, None, n_rounds=3, pipeline_depth=2, staleness=1,
            eval_every=0, predict_fn=None),
    }
    print(json.dumps({k: [i for i in instructions(v.compile().as_text())
                          if i[1] in ("all-to-all", "all-gather")]
                      for k, v in out.items()}))
""")


def test_sharded_exchange_sits_inside_the_fold():
    """On a four-device cohort mesh the fold's all_to_all and all_gathers
    carry ``fedcm.fold``.  The only collectives outside it are the cohort
    pass's gathers of the per-client loss row (``(C_pad,)`` floats, for the
    round's loss metric; the async program's pipeline fill has one more)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, "-c", _SHARDED, str(Path(__file__).parent)],
                       env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    for kind, collectives in got.items():
        ops = {c[1] for c in collectives}
        assert ops == {"all-to-all", "all-gather"}, (kind, collectives)
        outside = [c for c in collectives if scope_of(c[3]) != FOLD]
        assert outside and all(c[1:3] == ["all-gather", "f32[4]{0}"]
                               and c[3].endswith("/sharding_constraint") for c in outside), \
            (kind, outside)


_CACHED = textwrap.dedent("""
    import json, re, sys
    import jax, jax.numpy as jnp
    from repro.utils.compile_cache import key_on_metadata
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    def build(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2.0 + 1.0
        text = jax.jit(f).lower(jnp.ones((64,), jnp.float32)).compile().as_text()
        return sorted(set(re.findall(r'op_name="jit.f./([^/"]*)/', text)))

    seen = {}
    for keyed in (False, True):
        jax.config.update("jax_compilation_cache_dir", f"{sys.argv[1]}/{int(keyed)}")
        if keyed:
            key_on_metadata()
        seen[str(keyed)] = [build("fedcm.build_a"), build("fedcm.build_b")]
    print(json.dumps(seen))
""")


@pytest.fixture(scope="module")
def cached_builds(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", _CACHED, str(tmp_path_factory.mktemp("cache"))],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("keyed", [False, True])
def test_persistent_cache_answers_with_this_builds_scopes(cached_builds, keyed):
    """Two builds that differ only in a name scope share a persistent
    cache.  With JAX's default key the second loads the first's executable
    and reads the first's names; ``key_on_metadata`` keeps them apart."""
    first, second = cached_builds[str(keyed)]
    assert first == ["fedcm.build_a"]
    assert second == (["fedcm.build_b"] if keyed else ["fedcm.build_a"])


def test_cache_key_holds_source_paths_relative_to_the_checkout():
    from repro.utils import compile_cache

    before = (jax.config.jax_compilation_cache_include_metadata_in_key,
              jax.config.jax_hlo_source_file_canonicalization_regex)
    try:
        compile_cache.key_on_metadata()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        pattern = jax.config.jax_hlo_source_file_canonicalization_regex
        engine = compile_cache.REPO_ROOT / "src" / "repro" / "core" / "engine.py"
        assert re.sub(pattern, "", str(engine)) == "src/repro/core/engine.py"
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key", before[0])
        jax.config.update("jax_hlo_source_file_canonicalization_regex", before[1])
