"""The LM client on the engine's normal path (``run_rounds``), on CPU.

``FederatedTokens`` lays a per-client token corpus out as the engine's
``client_x``/``client_y`` and ``federated_lm_loss`` adapts the gathered
minibatch to the model; ``examples/federated_llm.py`` and the real-width
phase of ``chip_smoke.py`` both run through them.
"""
import importlib.util
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.configs.base import FedConfig, get_config, reduced
from repro.core import FederatedEngine
from repro.data import FederatedTokens, make_federated_lm_corpus
from repro.models import build_model, federated_lm_loss

EXAMPLE = Path(__file__).resolve().parents[1] / "examples" / "federated_llm.py"


def test_corpus_is_seeded_and_per_client():
    a = make_federated_lm_corpus(97, 3, 5, 11, seed=4)
    b = make_federated_lm_corpus(97, 3, 5, 11, seed=4)
    assert a.shape == (3, 5, 11) and a.dtype == np.int32
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 0 and a.max() < 97
    assert not np.array_equal(a[0], a[1])


def test_tokens_layout_is_next_token():
    seqs = make_federated_lm_corpus(50, 2, 3, 9, seed=0)
    data = FederatedTokens.from_sequences(seqs)
    assert data.client_x.shape == data.client_y.shape == (2, 3, 8)
    np.testing.assert_array_equal(np.asarray(data.client_x)[..., 1:],
                                  np.asarray(data.client_y)[..., :-1])


def test_federated_llm_example_runs_two_rounds():
    spec = importlib.util.spec_from_file_location("federated_llm", EXAMPLE)
    example = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(example)
    losses = example.main(rounds=2, echo=False)
    assert losses.shape == (2,)
    assert np.all(np.isfinite(losses))


def test_lm_client_kernel_path_matches_jnp():
    """Phase B of chip_smoke.py at a reduced width: the Pallas (interpret)
    round against the tree oracle's plain jnp round, per-round losses and
    params."""
    mcfg = replace(reduced(get_config("llama3.2-1b")), vocab_size=256)
    model = build_model(mcfg)
    data = FederatedTokens.from_sequences(
        make_federated_lm_corpus(mcfg.vocab_size, 4, 8, 33, seed=1)
    )
    out = {}
    for flat in (True, False):
        cfg = FedConfig(algo="fedcm", num_clients=4, cohort_size=2,
                        participation="fixed", local_steps=2, rounds=2,
                        use_flat_plane=flat)
        eng = FederatedEngine(cfg, federated_lm_loss(model), batch_size=2)
        state = eng.init(model.init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1))
        out[flat] = eng.run_rounds(state, data, 2)
    (s_k, m_k), (s_j, m_j) = out[True], out[False]
    np.testing.assert_allclose(np.asarray(m_k.loss), np.asarray(m_j.loss),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(s_k.params),
                    jax.tree_util.tree_leaves(s_j.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [1, 2])
def test_lm_loss_adapter_matches_model_loss(n):
    mcfg = replace(reduced(get_config("llama3.2-1b")), vocab_size=128)
    model = build_model(mcfg)
    params = model.init(jax.random.PRNGKey(n))
    data = FederatedTokens.from_sequences(
        make_federated_lm_corpus(mcfg.vocab_size, 1, n, 17, seed=n)
    )
    x, y = data.client_x[0], data.client_y[0]
    got = federated_lm_loss(model)(params, {"x": x, "y": y})
    want, _ = model.loss_fn(params, {"tokens": x, "labels": y})
    assert float(got) == float(want)
