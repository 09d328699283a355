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
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.fed_direction.kernel import fed_direction_flat
from repro.kernels.server_update.kernel import dequant_update_flat, server_update_flat
from repro.kernels.server_update.ops import _auto_block

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
