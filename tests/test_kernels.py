"""Kernel sweeps: every Pallas kernel vs its pure-jnp oracle across
shapes / dtypes (deliverable (c): per-kernel allclose)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.fed_direction.kernel import fed_direction_flat
from repro.kernels.fed_direction.ops import flat_direction_step
from repro.kernels.fed_direction.ref import fed_direction_ref
from repro.kernels.fed_direction.ref import fedcm_step_ref
from repro.kernels.server_update.ops import fused_server_step
from repro.kernels.server_update.ref import server_update_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_sequential_ref
from repro.models.mamba2 import ssd_chunked

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=3e-5, atol=3e-5)


# ----------------------------------------------------------------------
# fedcm blend oracle: the blend launches through fed_direction with coefs
# (η, α, 0, 1−α) — these tests pin that route to Algorithm 2 line 8–9 via
# fedcm_step_ref, independent of fed_direction's generic reference
# ----------------------------------------------------------------------


def _blend_coefs(alpha, eta):
    return jnp.asarray([eta, alpha, 0.0, 1.0 - alpha], jnp.float32)


@pytest.mark.parametrize("n", [5, 1023, 64 * 1024 + 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fed_direction_reproduces_fedcm_blend(n, dtype):
    x = jnp.asarray(RNG.normal(size=(n,)), dtype)
    g = jnp.asarray(RNG.normal(size=(n,)), dtype)
    d = jnp.asarray(RNG.normal(size=(n,)), dtype)
    out = fed_direction_flat(x, g, (d,), _blend_coefs(0.1, 0.05))
    ref = fedcm_step_ref(x, g, d, 0.1, 0.05)
    assert out.dtype == x.dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("alpha,eta", [(0.0, 0.1), (1.0, 0.1), (0.1, 0.0), (0.37, 1.3)])
def test_fedcm_blend_hyperparam_edges(alpha, eta):
    x = jnp.asarray(RNG.normal(size=(333,)), jnp.float32)
    g = jnp.asarray(RNG.normal(size=(333,)), jnp.float32)
    d = jnp.asarray(RNG.normal(size=(333,)), jnp.float32)
    np.testing.assert_allclose(
        fed_direction_flat(x, g, (d,), _blend_coefs(alpha, eta)),
        fedcm_step_ref(x, g, d, alpha, eta),
        rtol=1e-6, atol=1e-6,
    )


def test_fedcm_blend_bf16_params_keep_f32_momentum_precision():
    """Regression (dtype fidelity): bf16 params with f32 g/Δ must match the
    f32 reference — the retired wrapper once cast g/Δ to bf16 BEFORE the
    kernel, truncating the momentum the body was about to upcast anyway.
    The fed_direction route must preserve the contract."""
    x = jnp.asarray(RNG.normal(size=(4097,)), jnp.bfloat16)
    g = jnp.asarray(RNG.normal(size=(4097,)), jnp.float32)
    d = jnp.asarray(RNG.normal(size=(4097,)) * 1e-3, jnp.float32)
    out = fed_direction_flat(x, g, (d,), _blend_coefs(0.1, 0.05))
    ref = fedcm_step_ref(x, g, d, 0.1, 0.05)  # blends in full f32
    assert out.dtype == jnp.bfloat16
    # the kernel must agree with the f32-blend reference EXACTLY (both round
    # the same f32 value to bf16 once, at the end)
    np.testing.assert_array_equal(
        np.asarray(out, np.float32), np.asarray(ref, np.float32)
    )


def test_fedcm_blend_empty_tail_padding_is_dropped():
    """Non-block-multiple sizes: the padded tail must never leak into the
    output (output length and values exact for n = 1 and n = block+1)."""
    for n in (1, 64 * 1024 + 1):
        x = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
        g = jnp.ones((n,), jnp.float32)
        d = jnp.zeros((n,), jnp.float32)
        out = fed_direction_flat(x, g, (d,), _blend_coefs(1.0, 0.5))
        assert out.shape == (n,)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) - 0.5,
                                   rtol=1e-6, atol=1e-6)


# ----------------------------------------------------------------------
# fed_direction (generalized fused local step)
# ----------------------------------------------------------------------

# (η, c_g, c_x, c_aux...) per algorithm family, exercising 0/1/2 aux buffers
DIRECTION_CASES = [
    ("sgd", 0, [0.05, 1.0, 0.0]),
    ("blend", 1, [0.05, 0.1, 0.0, 0.9]),
    ("scaffold", 2, [0.05, 1.0, 0.0, -1.0, 1.0]),
    ("feddyn", 2, [0.05, 1.0, 0.01, -1.0, -0.01]),
]


@pytest.mark.parametrize("name,n_aux,coefs", DIRECTION_CASES)
@pytest.mark.parametrize("n", [1, 5, 1023, 64 * 1024 + 3])
def test_fed_direction_sweep(name, n_aux, coefs, n):
    x = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    g = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    auxes = tuple(jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
                  for _ in range(n_aux))
    cf = jnp.asarray(coefs, jnp.float32)
    out = fed_direction_flat(x, g, auxes, cf)
    ref = fed_direction_ref(x, g, auxes, cf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fed_direction_mixed_dtype_operands(dtype):
    """bf16 plane with f32 momentum (and vice versa): operands go in raw,
    the body blends in f32, only the output is rounded to x.dtype."""
    n = 777
    x = jnp.asarray(RNG.normal(size=(n,)), dtype)
    g = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    m = jnp.asarray(RNG.normal(size=(n,)), jnp.bfloat16)
    cf = jnp.asarray([0.1, 0.3, 0.0, 0.7], jnp.float32)
    out = fed_direction_flat(x, g, (m,), cf)
    ref = fed_direction_ref(x, g, (m,), cf)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=1e-6, atol=1e-6)


def test_flat_direction_step_algorithm_dispatch():
    """ops-level dispatch resolves each spec's DirectionRow (named streams:
    momentum = the broadcast buffer, client_state = c_i / λ_i) into the
    right affine kernel launch."""
    from repro.configs.base import FedConfig

    n = 513
    x = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    g = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    m = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    c_i = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    x0 = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    lam = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    cfg = FedConfig(alpha=0.2, feddyn_alpha=0.05)
    eta = jnp.float32(0.1)

    # (per-client state plane, expected update) — the broadcast buffer m
    # doubles as scaffold's c, exactly as the engine feeds it
    cases = {
        "fedcm": (None, x - eta * (0.2 * g + 0.8 * m)),
        "fedavg": (None, x - eta * g),
        "fedavgm": (None, x - eta * g),
        "fedacg": (None, x - eta * g),
        "scaffold": (c_i, x - eta * (g - c_i + m)),
        "feddyn": (lam, x - eta * (g - lam + 0.05 * (x - x0))),
    }
    for name, (cst, ref) in cases.items():
        out = flat_direction_step(name, cfg, x, g, m, cst, x0, eta)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6, err_msg=name)
    with pytest.raises(KeyError):
        flat_direction_step("nope", cfg, x, g, m, None, x0, eta)


def test_flat_direction_step_escape_hatch_spec():
    """A spec with a non-affine direction_fn bypasses the kernel but keeps
    the same x ← x − η_l·v contract on flat buffers."""
    from repro.configs.base import FedConfig
    from repro.core import AlgorithmSpec

    n = 257
    x = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    g = jnp.asarray(RNG.normal(size=(n,)), jnp.float32)
    spec = AlgorithmSpec(
        name="_signsgd_toy", direction_row=None,
        direction_fn=lambda cfg, m, cst, xx, x0, gg: jnp.sign(gg),
    )
    out = flat_direction_step(spec, FedConfig(), x, g, None, None, x, jnp.float32(0.1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x - 0.1 * jnp.sign(g)),
                               rtol=1e-6, atol=1e-7)


# ----------------------------------------------------------------------
# server_update (fused masked mean + momentum EMA + param step)
# ----------------------------------------------------------------------

SERVER_CASES = [
    # (C, P) plane shapes incl. non-block-multiple and tiny planes
    (1, 1),
    (3, 129),
    (8, 1000),
    (5, 16 * 1024 + 7),
]


@pytest.mark.parametrize("C,P", SERVER_CASES)
@pytest.mark.parametrize("masked", [False, True])
def test_server_update_sweep(C, P, masked):
    deltas = jnp.asarray(RNG.normal(size=(C, P)), jnp.float32)
    mask = np.ones(C, bool)
    if masked and C > 1:
        mask[-1] = False
    w = jnp.asarray(mask, jnp.float32)
    wn = w / jnp.sum(w)
    x = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    m = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    out = fused_server_step(deltas, wn, x, m, 0.9, 0.1, -2.0)
    coefs = jnp.asarray([0.9, 0.1, -2.0, 1.0], jnp.float32)
    ref = server_update_ref(deltas, wn, x, m, coefs)
    for o, r in zip(out, ref):
        assert o.shape == (P,)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-5, atol=2e-6)
    # masked-out client must contribute nothing
    if masked and C > 1:
        garbage = deltas.at[-1].set(1e9)
        out_g = fused_server_step(garbage, wn, x, m, 0.9, 0.1, -2.0)
        for o, og in zip(out, out_g):
            np.testing.assert_array_equal(np.asarray(o), np.asarray(og))


@pytest.mark.parametrize("write_x,write_m", [(True, False), (False, True),
                                             (False, False)])
def test_server_update_reduced_outputs(write_x, write_m):
    """A pass that structurally skips the param step / momentum EMA drops
    the output (and its input read) from the launch: the emitted subset is
    bitwise the full launch's, skipped slots come back None."""
    C, P = 4, 1000
    deltas = jnp.asarray(RNG.normal(size=(C, P)), jnp.float32)
    wn = jnp.full((C,), 0.25, jnp.float32)
    x = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    m = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    full = fused_server_step(deltas, wn, x, m, 0.9, 0.1, -2.0)
    part = fused_server_step(deltas, wn, x, m, 0.9, 0.1, -2.0,
                             write_x=write_x, write_m=write_m)
    for keep, p_out, f_out in zip((write_x, write_m, True), part, full):
        if keep:
            np.testing.assert_array_equal(np.asarray(p_out), np.asarray(f_out))
        else:
            assert p_out is None


def test_server_update_momentum_dtype_override():
    C, P = 4, 300
    deltas = jnp.asarray(RNG.normal(size=(C, P)), jnp.float32)
    wn = jnp.full((C,), 0.25, jnp.float32)
    x = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    m = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    new_x, new_m, mean = fused_server_step(
        deltas, wn, x, m, 0.0, -2.0, 1.0, m_dtype=jnp.bfloat16)
    assert new_m.dtype == jnp.bfloat16
    assert new_x.dtype == jnp.float32 and mean.dtype == jnp.float32


@pytest.mark.parametrize("gamma", [0.0, 0.5, 0.9, 1.0])
def test_server_update_staleness_discount(gamma):
    """The SMEM discount scalar scales the EMA/step inputs but NOT the
    emitted mean (metrics must see the cohort's actual delta)."""
    C, P = 3, 777
    deltas = jnp.asarray(RNG.normal(size=(C, P)), jnp.float32)
    wn = jnp.full((C,), 1.0 / C, jnp.float32)
    x = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    m = jnp.asarray(RNG.normal(size=(P,)), jnp.float32)
    new_x, new_m, mean = fused_server_step(
        deltas, wn, x, m, 0.7, -1.5, 2.0, discount=gamma)
    ref = server_update_ref(
        deltas, wn, x, m, jnp.asarray([0.7, -1.5, 2.0, gamma], jnp.float32))
    for o, r in zip((new_x, new_m, mean), ref):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=2e-5, atol=2e-6)
    # mean is undiscounted: recompute from raw inputs
    raw_mean = np.tensordot(np.asarray(wn), np.asarray(deltas), axes=(0, 0))
    np.testing.assert_allclose(np.asarray(mean), raw_mean, rtol=2e-5, atol=2e-6)
    if gamma == 1.0:  # γ=1 must be bitwise the undiscounted form
        base = fused_server_step(deltas, wn, x, m, 0.7, -1.5, 2.0)
        for o, b in zip((new_x, new_m, mean), base):
            np.testing.assert_array_equal(np.asarray(o), np.asarray(b))


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

ATTN_CASES = [
    # (B, Sq, Skv, H, Hkv, hd, causal, window, q_offset)
    (2, 64, 64, 4, 2, 32, True, None, 0),
    (1, 100, 100, 4, 4, 16, True, None, 0),     # ragged vs block
    (1, 128, 128, 2, 1, 32, True, 17, 0),       # sliding window (MQA)
    (1, 96, 96, 2, 2, 64, False, None, 0),      # bidirectional (encoder)
    (2, 1, 200, 4, 2, 32, True, None, 199),     # decode: 1 query vs deep KV
    (1, 257, 257, 8, 2, 128, True, None, 0),    # hd=128 MXU-width
    (1, 64, 64, 4, 2, 32, True, 1, 0),          # window=1 (self only)
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(case, dtype):
    B, Sq, Skv, H, Hkv, hd, causal, window, off = case
    q = jnp.asarray(RNG.normal(size=(B, Sq, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Skv, Hkv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Skv, Hkv, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, q_offset=off, bq=32, bkv=32)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window, q_offset=off)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


def test_flash_matches_model_layer_attention():
    """The kernel must agree with the model's attend_direct (GQA grouping)."""
    from repro.models.layers import attend_direct

    B, S, H, Hkv, hd = 2, 48, 8, 2, 32
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)), jnp.float32)
    pos = jnp.arange(S)
    msk = (pos[:, None] >= pos[None, :])[None, None]
    ref = attend_direct(q, k, v, msk, hd**-0.5)
    out = flash_attention(q, k, v, causal=True, bq=16, bkv=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=3e-5, atol=3e-5)


# ----------------------------------------------------------------------
# ssd scan
# ----------------------------------------------------------------------

SSD_CASES = [
    # (B, S, H, P, N, chunk)
    (2, 64, 3, 16, 8, 16),
    (1, 100, 2, 32, 16, 32),   # ragged
    (1, 37, 1, 8, 4, 16),      # shorter than 2 chunks
    (1, 128, 4, 64, 32, 64),   # production-ish tile
    (2, 16, 2, 8, 8, 16),      # single chunk
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_vs_sequential(case, dtype):
    B, S, H, P, N, chunk = case
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), dtype)
    dt = jnp.asarray(np.abs(RNG.normal(size=(B, S, H))) * 0.1 + 0.01, jnp.float32)
    A = -jnp.asarray(np.abs(RNG.normal(size=(H,))) + 0.5, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), dtype)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), dtype)
    y_ker, st_ker = ssd(x, dt, A, Bm, Cm, chunk=chunk)
    y_ref, st_ref = ssd_sequential_ref(x, dt, A, Bm, Cm)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(y_ker, np.float32), np.asarray(y_ref, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(st_ker), np.asarray(st_ref), **tol)


def test_ssd_chunk_invariance():
    """The chunk size is an implementation detail — outputs must not move."""
    B, S, H, P, N = 1, 96, 2, 16, 8
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.normal(size=(B, S, H))) * 0.1 + 0.01, jnp.float32)
    A = -jnp.asarray(np.abs(RNG.normal(size=(H,))) + 0.5, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    y16, _ = ssd(x, dt, A, Bm, Cm, chunk=16)
    y48, _ = ssd(x, dt, A, Bm, Cm, chunk=48)
    np.testing.assert_allclose(np.asarray(y16), np.asarray(y48), rtol=2e-4, atol=2e-4)


def test_ssd_kernel_vs_model_chunked():
    """kernel == the model's jnp chunked path (the integration contract)."""
    B, S, H, P, N, chunk = 2, 80, 2, 16, 8, 16
    x = jnp.asarray(RNG.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(np.abs(RNG.normal(size=(B, S, H))) * 0.1 + 0.01, jnp.float32)
    A = -jnp.asarray(np.abs(RNG.normal(size=(H,))) + 0.5, jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, S, N)), jnp.float32)
    y_k, st_k = ssd(x, dt, A, Bm, Cm, chunk=chunk)
    y_m, st_m = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_m), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st_k), np.asarray(st_m), rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------------------
# interpret-vs-Mosaic choice: made per launch, never at import
# ----------------------------------------------------------------------


@pytest.mark.parametrize("platform,expected", [("cpu", True), ("tpu", False)])
def test_interpret_mode_follows_backend(monkeypatch, platform, expected):
    from repro import kernels

    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert kernels.interpret_mode() is expected


def test_interpret_mode_refuses_other_platforms(monkeypatch):
    from repro import kernels

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        kernels.interpret_mode()


def test_importing_the_engine_starts_no_backend():
    import os
    import subprocess
    import sys

    code = ("import repro.core, repro.launch.fed_train\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
