"""Mosaic compiles of the main-path Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler compiles for a ``v5e:2x2`` topology
that is only described, so a kernel the chip would refuse (unaligned
tiles, too much VMEM, a program that does not fit HBM) fails here, at no
chip time.  Nothing runs, so these tests say nothing about results or
speed — ``tests/test_kernels.py`` holds the kernels to their oracles in
interpret mode.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and under pytest-xdist every
worker imports this file.  The persistent compilation cache is off around
the compiles (an entry compiled for a described chip cannot be read back
without one).
"""
import base64
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import kernels
from repro.configs.base import FedConfig, ModelConfig
from repro.core import FederatedEngine, FlatSpec
from repro.kernels.fed_direction.kernel import fed_direction_flat
from repro.kernels.server_update.kernel import dequant_update_flat, server_update_flat
from repro.kernels.server_update.ops import _auto_block
from repro.models import build_model, federated_lm_loss
from repro.models.layers import init_moe, moe_block
from repro.models.small import classification_loss, mlp_classifier
from repro.utils.compat import device_mesh

# llama3.2-1b at its published widths, 2 layers, an eighth of the vocab:
# the Phase B client of chip_smoke.py (an odd length: the ragged tail pads)
P_LM = 155_000_003
# the paper's Setting I client (mlp 32-128-128-10): Phase A of chip_smoke.py
P_MLP = 22_026


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _hlo_of(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("n_aux", [0, 1, 2])
def test_fed_direction_compiles(one_chip, n_aux, dtype):
    plane = jax.ShapeDtypeStruct((P_LM,), dtype, sharding=one_chip)
    coefs = jax.ShapeDtypeStruct((3 + n_aux,), jnp.float32, sharding=one_chip)

    def step(x, g, coefs, *auxes):
        return fed_direction_flat(x, g, auxes, coefs, interpret=False)

    hlo = _hlo_of(step, plane, plane, coefs, *([plane] * n_aux))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("cohort,n", [
    (10, P_MLP), (64, P_MLP),
    (28, -(-P_MLP // 4)),  # one chip's column block of the 4-chip fold
    (2, P_LM),
], ids=["c10", "c64", "c28-column", "c2-lm"])
def test_server_update_compiles(one_chip, cohort, n):
    f32 = jnp.float32
    deltas = jax.ShapeDtypeStruct((cohort, n), f32, sharding=one_chip)
    wn = jax.ShapeDtypeStruct((cohort,), f32, sharding=one_chip)
    plane = jax.ShapeDtypeStruct((n,), f32, sharding=one_chip)
    coefs = jax.ShapeDtypeStruct((4,), f32, sharding=one_chip)

    def fold(deltas, wn, x, m, coefs):
        return server_update_flat(deltas, wn, x, m, coefs, interpret=False,
                                  block_elems=_auto_block(n))

    assert "tpu_custom_call" in _hlo_of(fold, deltas, wn, plane, plane, coefs)


@pytest.mark.parametrize("wire", [jnp.int8, jnp.bfloat16], ids=["int8", "bf16"])
def test_dequant_fold_compiles(one_chip, wire):
    cohort, f32 = 10, jnp.float32
    q = jax.ShapeDtypeStruct((cohort, P_MLP), wire, sharding=one_chip)
    scale = jax.ShapeDtypeStruct((cohort, 1), f32, sharding=one_chip)
    wn = jax.ShapeDtypeStruct((cohort,), f32, sharding=one_chip)
    plane = jax.ShapeDtypeStruct((P_MLP,), f32, sharding=one_chip)
    coefs = jax.ShapeDtypeStruct((4,), f32, sharding=one_chip)

    def fold(q, scale, wn, x, m, coefs):
        return dequant_update_flat(q, scale, wn, x, m, coefs, interpret=False,
                                   block_elems=_auto_block(P_MLP))

    hlo = _hlo_of(fold, q, scale, wn, plane, plane, coefs)
    assert "tpu_custom_call" in hlo


# a one-layer LM client that compiles in seconds; its 110,784 parameters
# are no multiple of either kernel's block
TINY_LM = ModelConfig(name="tiny-lm", family="dense", n_layers=1, d_model=64,
                      n_heads=2, n_kv_heads=1, head_dim=32, d_ff=256,
                      vocab_size=512, mlp_type="gelu", dtype="bfloat16",
                      param_dtype="float32")
KERNEL_SCOPES = ("jit(fed_direction_flat)", "jit(server_update_flat)")
_INSTR = re.compile(r"^\s*(?:ROOT )?%((?:pad|slice|copy)[\w.\-]*) = \w+\[([\d,]*)\]")


def _plane_copies(hlo: str, min_elems: int):
    """pad, slice and copy instructions (fused or not) of at least
    ``min_elems`` elements whose ``op_name`` lies under a kernel's jit."""
    found = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        op = re.search(r'op_name="([^"]*)"', line)
        if not (m and op and any(s in op.group(1) for s in KERNEL_SCOPES)):
            continue
        elems = 1
        for d in filter(None, m.group(2).split(",")):
            elems *= int(d)
        if elems >= min_elems:
            found.append(m.group(1))
    return found


@pytest.mark.parametrize("shards", [1, 4], ids=["one-chip", "mesh-2x2"])
def test_kernel_round_copies_no_plane(topo, monkeypatch, shards):
    """The kernel-path round of an LM client whose parameter count is not
    aligned: the engine lays the plane out at the kernels' block length,
    so no launch pads its operands or slices its outputs, on one chip or
    in the scattered fold's column chunks.  The (C, 128) lane padding of
    the cohort weights is not a plane and is not counted."""
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    model = build_model(TINY_LM)
    cfg = FedConfig(algo="fedcm", num_clients=4, cohort_size=shards, local_steps=2,
                    participation="fixed")
    if shards > 1:
        mesh = device_mesh(topo.devices[:shards], ("clients",))
        where = NamedSharding(mesh, PartitionSpec())
    else:
        mesh, where = None, SingleDeviceSharding(topo.devices[0])
    eng = FederatedEngine(cfg, federated_lm_loss(model), batch_size=2, cohort_mesh=mesh)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(eng.init, params, jax.random.PRNGKey(1))
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=where), state)
    tokens = jax.ShapeDtypeStruct((4, 8, 64), jnp.int32, sharding=where)
    hlo = eng._run_rounds.lower(state, tokens, tokens, n_rounds=1).compile().as_text()
    P = FlatSpec.from_tree(params).size
    assert P % 1024 and hlo.count("tpu_custom_call") == 2
    assert _plane_copies(hlo, P // shards) == []


_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_FUSION = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) fusion\(.*calls=%([\w.\-]+)")
_ARRAY = re.compile(r"\w+\[([\d,]*)\]")


def _fusion_pads(hlo: str, min_elems: int):
    """The number of pads in each fusion whose output (its largest array)
    has at least ``min_elems`` elements."""
    bodies, name = {}, None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name:
            bodies[name].append(line)
    pads = []
    for lines in bodies.values():
        for line in lines:
            m = _FUSION.match(line)
            if m and max(math.prod(int(d) for d in filter(None, dims.split(",")))
                         for dims in _ARRAY.findall(m.group(1))) >= min_elems:
                pads.append(sum(" pad(" in body for body in bodies.get(m.group(2), [])))
    return pads


def test_gradient_returns_to_the_plane_without_a_pad_per_leaf(one_chip, monkeypatch):
    """The kernel-path local steps of a 44-leaf client with weight decay,
    under the cohort ``vmap`` and the K-step ``scan``: the gradient returns
    to the plane as ``unravel``'s backward, one concatenate of the leaf
    gradients, so no plane-sized fusion holds a pad for each leaf (autodiff
    of the view's slices would sum one plane-sized pad per leaf)."""
    monkeypatch.setattr(kernels, "interpret_mode", lambda: False)
    model = mlp_classifier((8,) + (32,) * 21 + (4,))
    cfg = FedConfig(algo="fedcm", num_clients=4, cohort_size=2, local_steps=2,
                    participation="fixed", weight_decay=1e-3)
    eng = FederatedEngine(cfg, classification_loss(model.apply), batch_size=4)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(eng.init, params, jax.random.PRNGKey(1))
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), state)
    cx = jax.ShapeDtypeStruct((4, 16, 8), jnp.float32, sharding=one_chip)
    cy = jax.ShapeDtypeStruct((4, 16), jnp.int32, sharding=one_chip)
    hlo = eng._run_rounds.lower(state, cx, cy, n_rounds=1).compile().as_text()
    spec = FlatSpec.from_tree(params)
    assert len(spec.leaves) == 44
    pads = _fusion_pads(hlo, spec.size)
    assert pads and max(pads) <= 1, pads


# Mellum2's expert layer at its published widths, 8 of 64 experts held,
# top-8 over 2,048 tokens: 16,384 (token, choice) rows
MOE = ModelConfig(name="mellum2-experts", family="moe", n_layers=1, d_model=2304, n_heads=32,
                  n_kv_heads=4, head_dim=128, d_ff=896, vocab_size=512, n_experts=64,
                  n_experts_held=8, top_k=8, capacity_factor=None, router_z_loss=0.0,
                  load_balance_loss=0.0, dtype="bfloat16")
_GRID = re.compile(r"iteration_bounds = array<i64: ([^>]*)>")
DYNAMIC = -(2**63)  # a grid extent the kernel reads at run time


def _grouped_kernel_grids(hlo: str):
    """The grid of each grouped-matmul kernel (``ragged-dot-*``), read from
    its Mosaic body."""
    grids = []
    for line in hlo.splitlines():
        if re.match(r"\s*(?:ROOT )?%ragged-dot-none", line):
            body = base64.b64decode(re.search(r'"body":"([^"]+)"', line).group(1)).decode("latin1")
            grids.append([int(v) for v in _GRID.search(body).group(1).split(",")])
    return grids


def test_dropless_experts_compile_to_grouped_kernels(one_chip):
    """The dropless layer's forward and backward, mapped over a cohort of
    two clients as the engine maps them: every grouped matmul (per client 3
    forward, 3 row gradients, 3 weight gradients) compiles to a Mosaic
    kernel whose grid takes its extent over the rows from the group sizes
    at run time, so the work follows the rows routed to the held experts,
    not the T × top_k rows the buffers are sized for."""

    def shaped(a, lead=(2,)):
        return jax.ShapeDtypeStruct(lead + a.shape, a.dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(shaped, jax.eval_shape(lambda k: init_moe(k, MOE), jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((2, 1, 2048, MOE.d_model), jnp.bfloat16, sharding=one_chip)

    def loss(p, x):
        return jnp.sum(jnp.square(moe_block(p, x, cfg=MOE)[0].astype(jnp.float32)))

    grids = _grouped_kernel_grids(_hlo_of(jax.vmap(jax.grad(loss, argnums=(0, 1))), params, x))
    assert len(grids) == 2 * 9
    assert all(g.count(DYNAMIC) == 1 for g in grids), grids

